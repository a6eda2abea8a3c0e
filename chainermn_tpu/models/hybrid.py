"""Decoder LM with a per-layer pattern of mixers and feed-forwards.

The block vocabulary the large hybrid models share, beside (not inside)
``TransformerBlock``: RMSNorm pre-norm residual blocks, SwiGLU feed-forwards,
and per layer one MIXER of

* ``"kda"`` — Kimi Delta Attention (Kimi Linear, arXiv:2510.26692): a gated
  delta-rule linear attention with a per-channel decay. Per head the state
  ``S ∈ R^{dk×dv}`` follows
  ``S_t = (I − β_t k_t k_tᵀ) Diag(α_t) S_{t−1} + β_t k_t v_tᵀ``,
  ``o_t = S_tᵀ q_t``. Prefill runs the chunk-parallel WY/UT form of that
  recurrence (:func:`kda_chunk`, chunk 64), decode the one-step form
  (:func:`kda_step`); both are f32.
* ``"mla"`` — multi-head latent attention (DeepSeek-V2): the cache holds a
  512-value latent and one shared rotary key per token; prefill expands
  them to per-head keys and values, decode uses the absorbed form.

and one FEED-FORWARD of ``"dense"`` (SwiGLU) or ``"moe"`` (this chip's share
of a routed expert layer, ``parallel/expert_share.py``, plus a shared expert).

Serving contract (``serving/state_cache.py`` reads ``declares_cache``): with
``decode=True`` the ``cache`` collection holds, slot-major, whatever the
layers declare — a recurrent state and a convolution tail per KDA layer, a
latent page per MLA layer — and one cursor vector ``idx`` at the root.
``__call__(tokens [B, L], lengths=[B], live=[B])`` advances row ``b`` by
``lengths[b]`` tokens (right-padded rows: a recurrence integrates padding
unless told not to, so positions at or past the length get ``β = 0``,
``α = 1`` and leave the state exactly as it was) and leaves rows that are
not ``live`` untouched apart from page columns past their cursor.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from chainermn_tpu.parallel.expert_share import HeldExperts, RouteStats

__all__ = ["HybridLM", "HybridBlock", "KDAMixer", "MLAMixer", "RMSNorm",
           "SwiGLU", "kda_chunk", "kda_step", "layer_pattern"]

_HI = jax.lax.Precision.HIGHEST
KDA_CHUNK = 64
_SUB = 16        # sub-block inside a chunk: keeps every exponent <= 0


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.dtype)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), -1, keepdims=True) + self.eps)
        return (y * scale.astype(jnp.float32)).astype(self.dtype)


class SwiGLU(nn.Module):
    d_ff: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                         param_dtype=self.dtype, name=name)
        h = nn.silu(dense(self.d_ff, "gate")(x)) * dense(self.d_ff, "up")(x)
        return dense(x.shape[-1], "down")(h)


# ---------------------------------------------------------------------------
# KDA: the recurrence in its two forms
# ---------------------------------------------------------------------------

def kda_step(q, k, v, g, beta, state):
    """One token of the recurrence for every row and head, in f32.

    q, k, g ``[B, H, dk]``; v ``[B, H, dv]``; beta ``[B, H]``; state
    ``[B, H, dk, dv]``. Products against the state are multiply-and-sum on
    the vector unit: a matrix unit would round the f32 state's operands."""
    s = state * jnp.exp(g)[..., None]
    ks = (k[..., None] * s).sum(-2)
    s = s + (beta[..., None] * k)[..., None] * (v - ks)[..., None, :]
    o = (q[..., None] * s).sum(-2)
    return o, s


def _pair_matrices(q, k, gc):
    """Intra-chunk decayed products for one chunk: ``A_kk[t, i] = Σ_d k_t
    k_i exp(G_t − G_i)`` for ``i < t`` and ``A_qk[t, i]`` likewise with
    ``q_t`` for ``i ≤ t``; zero elsewhere. ``gc`` is the inclusive cumulative
    log decay inside the chunk. Sub-blocks of 16 keep every exponent ≤ 0:
    diagonal blocks take the difference directly, a block below the diagonal
    splits it at the sub-block's start."""
    c = q.shape[-2]
    nb = c // _SUB
    blk = lambda a: a.reshape(a.shape[:-2] + (nb, _SUB, a.shape[-1]))
    qb, kb, gb = blk(q), blk(k), blk(gc)
    diff = gb[..., :, None, :] - gb[..., None, :, :]      # [.., nb, t, i, d]
    tri = jnp.tril(jnp.ones((_SUB, _SUB), bool))
    decay = jnp.exp(jnp.where(tri[..., None], diff, -jnp.inf))
    ki = kb[..., None, :, :] * decay
    kk_d = (kb[..., :, None, :] * ki).sum(-1)
    qk_d = (qb[..., :, None, :] * ki).sum(-1)
    kk_d = jnp.where(jnp.tril(tri, -1), kk_d, 0.0)
    rows_kk, rows_qk = [], []
    for i in range(nb):
        lo = i * _SUB
        parts_kk, parts_qk = [], []
        if i:
            g0 = gc[..., lo - 1:lo, :]                     # start of block i
            kj = k[..., :lo, :] * jnp.exp(g0 - gc[..., :lo, :])
            scale = jnp.exp(gb[..., i, :, :] - g0)
            parts_kk.append(jnp.einsum(
                "...td,...id->...ti", kb[..., i, :, :] * scale, kj,
                precision=_HI))
            parts_qk.append(jnp.einsum(
                "...td,...id->...ti", qb[..., i, :, :] * scale, kj,
                precision=_HI))
        parts_kk.append(kk_d[..., i, :, :])
        parts_qk.append(qk_d[..., i, :, :])
        pad = jnp.zeros(q.shape[:-2] + (_SUB, c - lo - _SUB), q.dtype)
        rows_kk.append(jnp.concatenate(parts_kk + [pad], -1))
        rows_qk.append(jnp.concatenate(parts_qk + [pad], -1))
    return jnp.concatenate(rows_kk, -2), jnp.concatenate(rows_qk, -2)


def kda_chunk(q, k, v, g, beta, state, chunk: int = KDA_CHUNK):
    """The same recurrence over ``L`` tokens, chunk-parallel (WY/UT form).

    q, k, g ``[B, L, H, dk]``; v ``[B, L, H, dv]``; beta ``[B, L, H]``;
    state ``[B, H, dk, dv]``; all f32, ``L`` a multiple of ``chunk`` (the
    caller pads with ``β = 0, g = 0``). Inside a chunk with entry state
    ``S₀`` and ``Γ_t = exp(Σ_{i≤t} g_i)``: ``S_t = Diag(Γ_t) S₀ + Σ_{i≤t}
    Diag(Γ_t/Γ_i) k_i u_iᵀ`` where the pseudo-values solve the unit lower
    triangular system ``(I + Diag(β) A_kk) U = Diag(β)(V − K⁺ S₀)``,
    ``K⁺ = Γ ⊙ K`` — solved once for both right-hand sides, so the state
    enters through products only. Chunks run under one scan (the pair
    matrices of all chunks at once would be a gigabyte at serving sizes).
    Returns ``(o [B, L, H, dv], final state)``."""
    b, l, h, dk = q.shape
    dv = v.shape[-1]
    n = l // chunk
    # [n, B, H, C, ·]
    split = lambda a: jnp.moveaxis(
        a.reshape((b, n, chunk) + a.shape[2:]), (1, 3), (0, 2))
    eye = jnp.eye(chunk, dtype=q.dtype)

    def body(s, xs):
        q, k, v, g, beta = xs
        gc = jnp.cumsum(g, axis=-2)
        a_kk, a_qk = _pair_matrices(q, k, gc)
        x = jax.scipy.linalg.solve_triangular(
            eye + beta * a_kk,
            jnp.concatenate([beta * v, beta * k * jnp.exp(gc)], -1),
            lower=True, unit_diagonal=True)
        uv, w = x[..., :dv], x[..., dv:]
        g_last = gc[..., -1:, :]
        u = uv - jnp.einsum("bhcd,bhdv->bhcv", w, s, precision=_HI)
        o = (jnp.einsum("bhcd,bhdv->bhcv", q * jnp.exp(gc), s, precision=_HI)
             + jnp.einsum("bhci,bhiv->bhcv", a_qk, u, precision=_HI))
        s = jnp.exp(g_last[..., 0, :])[..., None] * s + jnp.einsum(
            "bhcd,bhcv->bhdv", k * jnp.exp(g_last - gc), u, precision=_HI)
        return s, o

    state, o = jax.lax.scan(
        body, state, (split(q), split(k), split(v), split(g),
                      split(beta[..., None])))
    # [n, B, H, C, dv] -> [B, L, H, dv]
    return jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, l, h, dv), state


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


class KDAMixer(nn.Module):
    n_heads: int
    d_k: int
    d_v: int
    conv: int = 4
    lower_bound: float = -5.0
    eps: float = 1e-6
    dtype: Any = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, lengths, live):
        b, l, d = x.shape
        h, dk, dv = self.n_heads, self.d_k, self.d_v
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                         param_dtype=self.dtype, name=name)
        proj = jnp.concatenate([dense(h * dk, "q_proj")(x),
                                dense(h * dk, "k_proj")(x),
                                dense(h * dv, "v_proj")(x)], -1)
        conv_w = jnp.concatenate([
            self.param(n, nn.initializers.lecun_normal(), (self.conv, w),
                       self.dtype)
            for n, w in (("q_conv", h * dk), ("k_conv", h * dk),
                         ("v_conv", h * dv))], -1)
        a_log = self.param("a_log", nn.initializers.zeros, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (h * dk,),
                             jnp.float32)
        f = dense(h * dk, "f_proj")(x).astype(jnp.float32) + dt_bias
        g = self.lower_bound * jax.nn.sigmoid(
            jnp.exp(a_log)[:, None] * f.reshape(b, l, h, dk))
        beta = jax.nn.sigmoid(dense(h, "b_proj")(x).astype(jnp.float32))
        gate = jax.nn.sigmoid(dense(h * dv, "g_proj")(x).astype(jnp.float32))

        tail_len = self.conv - 1
        if self.decode:
            state_v = self.variable("cache", "state", jnp.zeros,
                                    (b, h, dk, dv), jnp.float32)
            tail_v = self.variable("cache", "conv", jnp.zeros,
                                   (b, tail_len, proj.shape[-1]), self.dtype)
            state, tail = state_v.value, tail_v.value
        else:
            state = jnp.zeros((b, h, dk, dv), jnp.float32)
            tail = jnp.zeros((b, tail_len, proj.shape[-1]), self.dtype)
        # rows advance by their own length: 0 for a row that is not live
        adv = jnp.where(live, lengths, 0)
        real = jnp.arange(l)[None] < adv[:, None]               # [B, L]
        g = jnp.where(real[..., None, None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)

        ext = jnp.concatenate([tail.astype(proj.dtype), proj], 1)
        ext32, conv32 = ext.astype(jnp.float32), conv_w.astype(jnp.float32)
        mixed = nn.silu(sum(ext32[:, j:j + l] * conv32[j]
                            for j in range(self.conv)))
        q, k, v = jnp.split(mixed, [h * dk, 2 * h * dk], -1)
        q = _l2norm(q.reshape(b, l, h, dk), self.eps) * dk ** -0.5
        k = _l2norm(k.reshape(b, l, h, dk), self.eps)
        v = v.reshape(b, l, h, dv)
        if l == 1:
            with jax.named_scope("kda_step"):
                o, state = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                    beta[:, 0], state)
                o = o[:, None]
        else:
            with jax.named_scope("kda_chunk"):
                pad = -l % KDA_CHUNK
                padded = lambda a: jnp.pad(
                    a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                o, state = kda_chunk(*map(padded, (q, k, v, g, beta)), state)
                o = o[:, :l]
        if self.decode:
            state_v.value = state
            # the tail after a row's LAST REAL token: rows adv..adv+2 of ext
            at = adv[:, None] + jnp.arange(tail_len)[None]
            tail_v.value = jnp.take_along_axis(
                ext, at[..., None], axis=1).astype(self.dtype)
        o = RMSNorm(self.eps, jnp.float32, name="o_norm")(o)
        o = (o.reshape(b, l, h * dv) * gate).astype(self.dtype)
        return dense(d, "o_proj")(o)


# ---------------------------------------------------------------------------
# MLA
# ---------------------------------------------------------------------------

def rope_interleaved(x, positions, theta):
    """Rotate adjacent pairs ``(x_{2i}, x_{2i+1})`` of the last axis by
    ``positions · theta^{-2i/d}``. x ``[B, L, ..., d]``, positions
    ``[B, L]``."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = positions.astype(jnp.float32)[..., None] * freqs     # [B, L, d/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.reshape(x.shape)


class MLAMixer(nn.Module):
    n_heads: int
    d_nope: int
    d_rope: int
    d_v: int
    kv_rank: int
    rope_theta: float
    max_len: int
    eps: float = 1e-6
    dtype: Any = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, pos):
        b, l, d = x.shape
        h, dn, dr, dv, r = (self.n_heads, self.d_nope, self.d_rope,
                            self.d_v, self.kv_rank)
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                         param_dtype=self.dtype, name=name)
        positions = pos[:, None] + jnp.arange(l)[None]
        q = dense(h * (dn + dr), "q_proj")(x).reshape(b, l, h, dn + dr)
        q_nope = q[..., :dn]
        q_rope = rope_interleaved(q[..., dn:], positions,
                                  self.rope_theta).astype(self.dtype)
        kva = dense(r + dr, "kva_proj")(x)
        c = RMSNorm(self.eps, self.dtype, name="c_norm")(kva[..., :r])
        k_r = rope_interleaved(kva[..., r:], positions,
                               self.rope_theta).astype(self.dtype)
        ckv = jnp.concatenate([c, k_r], -1)                    # [B, L, r+dr]
        w_kvb = self.param("kvb_proj", nn.initializers.lecun_normal(),
                           (r, h * (dn + dv)), self.dtype)
        w_kvb = w_kvb.reshape(r, h, dn + dv)
        gate = jax.nn.sigmoid(dense(h, "g_proj")(x).astype(jnp.float32))
        scale = (dn + dr) ** -0.5
        if self.decode:
            page_v = self.variable("cache", "ckv", jnp.zeros,
                                   (b, self.max_len, r + dr), self.dtype)
        if l > 1:
            # prefill of a fresh slot (pos 0): the expanded form
            kv = jnp.einsum("blr,rhe->blhe", c, w_kvb)
            k_nope, v = kv[..., :dn], kv[..., dn:]
            s = (jnp.einsum("bqhe,bkhe->bhqk", q_nope, k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bqhe,bke->bhqk", q_rope, k_r,
                              preferred_element_type=jnp.float32)) * scale
            s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
            p = jax.nn.softmax(s, -1).astype(self.dtype)
            o = jnp.einsum("bhqk,bkhe->bqhe", p, v,
                           preferred_element_type=jnp.float32)
            if self.decode:
                page = page_v.value
                page_v.value = page.at[:, :l].set(ckv.astype(page.dtype))
        else:
            if not self.decode:
                raise ValueError("a one-token call needs decode=True")
            with jax.named_scope("mla_absorbed"):
                page = page_v.value
                page = page.at[jnp.arange(b), pos].set(
                    ckv[:, 0].astype(page.dtype), mode="drop")
                page_v.value = page
                q_lat = jnp.einsum("bhe,rhe->bhr", q_nope[:, 0],
                                   w_kvb[..., :dn]).astype(self.dtype)
                q_cat = jnp.concatenate([q_lat, q_rope[:, 0]], -1)
                s = jnp.einsum("bhc,btc->bht", q_cat, page,
                               preferred_element_type=jnp.float32) * scale
                seen = jnp.arange(page.shape[1])[None] <= pos[:, None]
                s = jnp.where(seen[:, None], s, -jnp.inf)
                p = jax.nn.softmax(s, -1).astype(self.dtype)
                o_lat = jnp.einsum("bht,btr->bhr", p, page[..., :r],
                                   preferred_element_type=jnp.float32)
                o = jnp.einsum("bhr,rhe->bhe", o_lat.astype(self.dtype),
                               w_kvb[..., dn:],
                               preferred_element_type=jnp.float32)[:, None]
        o = (o * gate[..., None]).astype(self.dtype).reshape(b, l, h * dv)
        return dense(d, "o_proj")(o)


# ---------------------------------------------------------------------------
# block and model
# ---------------------------------------------------------------------------

def layer_pattern(n_layers: int, group: int, first_dense: int
                  ) -> Tuple[Tuple[str, str], ...]:
    """Layer ``i`` mixes with MLA when ``(i + 1) % group == 0`` and KDA
    otherwise; the first ``first_dense`` layers have a dense feed-forward,
    the rest the routed one."""
    return tuple(("mla" if (i + 1) % group == 0 else "kda",
                  "dense" if i < first_dense else "moe")
                 for i in range(n_layers))


class HybridBlock(nn.Module):
    mixer: str
    ffn: str
    cfg: Any                     # HybridLM.dims(): the sizes, as a tuple
    decode: bool = False

    @nn.compact
    def __call__(self, x, pos, lengths, live):
        c = self.cfg
        y = RMSNorm(c.norm_eps, c.dtype, name="norm_mix")(x)
        if self.mixer == "kda":
            y = KDAMixer(c.n_heads, c.d_head, c.d_head, conv=c.conv_kernel,
                         lower_bound=c.kda_lower_bound, eps=c.norm_eps,
                         dtype=c.dtype, decode=self.decode,
                         name="kda")(y, lengths, live)
        else:
            y = MLAMixer(c.n_heads, c.d_nope, c.d_rope, c.d_head, c.kv_rank,
                         c.rope_theta, c.max_len, eps=c.norm_eps,
                         dtype=c.dtype, decode=self.decode,
                         name="mla")(y, pos)
        x = x + y
        y = RMSNorm(c.norm_eps, c.dtype, name="norm_ffn")(x)
        if self.ffn == "dense":
            return x + SwiGLU(c.d_ff, dtype=c.dtype, name="ffn")(y), None
        b, l, d = y.shape
        routes = (live[:, None]
                  & (jnp.arange(l)[None] < lengths[:, None])).reshape(-1)
        flat = y.reshape(b * l, d)
        routed, stats = HeldExperts(
            c.n_experts, c.held_lo, c.held_hi, c.d_expert, c.top_k,
            c.n_group, c.topk_group, c.routed_scale, c.norm_topk_prob,
            dtype=c.dtype, name="moe")(flat, routes)
        shared = SwiGLU(c.d_shared, dtype=c.dtype, name="shared")(flat)
        return x + (routed + shared).reshape(b, l, d), stats


class HybridLM(nn.Module):
    """See the module docstring. ``pattern`` is one ``(mixer, ffn)`` pair a
    layer (:func:`layer_pattern`); the expert fields describe the routed
    layers: ``n_experts`` routed over, ``held_lo:held_hi`` held here."""
    vocab: int
    d_model: int
    n_heads: int
    d_head: int
    pattern: Tuple[Tuple[str, str], ...]
    d_ff: int
    max_len: int
    d_nope: int = 128
    d_rope: int = 64
    kv_rank: int = 512
    rope_theta: float = 10000.0
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    n_experts: int = 0
    held_lo: int = 0
    held_hi: int = 0
    d_expert: int = 0
    d_shared: int = 0
    top_k: int = 8
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 1.0
    norm_topk_prob: bool = True
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32
    decode: bool = False

    #: serving/kv_cache.py: the pages are what the ``cache`` collection
    #: declares (recurrent state, convolution tail, latent page), not K/V
    declares_cache = True

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    def dims(self):
        """The sizes a block reads, as a plain tuple (a Module handed to a
        child as a field would be adopted as its submodule)."""
        names = [f.name for f in dataclasses.fields(self)
                 if f.name not in ("parent", "name", "pattern", "decode")]
        return collections.namedtuple("HybridDims", names)(
            *(getattr(self, n) for n in names))

    @nn.compact
    def __call__(self, tokens, pos_offset=None, lengths=None, live=None):
        b, l = tokens.shape
        if lengths is None:
            lengths = jnp.full((b,), l, jnp.int32)
        if live is None:
            live = jnp.ones((b,), bool)
        if self.decode:
            idx = self.variable("cache", "idx", jnp.zeros, (b,), jnp.int32)
            pos = idx.value if pos_offset is None else jnp.broadcast_to(
                jnp.asarray(pos_offset, jnp.int32), (b,))
        else:
            pos = jnp.zeros((b,), jnp.int32)
        x = nn.Embed(self.vocab, self.d_model, dtype=self.dtype,
                     param_dtype=self.dtype, name="tok_emb")(tokens)
        stats = RouteStats.zero()
        dims = self.dims()
        for i, (mixer, ffn) in enumerate(self.pattern):
            x, st = HybridBlock(mixer, ffn, dims, decode=self.decode,
                                name=f"block_{i}")(x, pos, lengths, live)
            if st is not None:
                stats = stats + st
        if self.decode:
            idx.value = pos + jnp.where(live, lengths, 0).astype(jnp.int32)
            if self.n_experts:
                # the serving step returns the collection with the tokens,
                # and the engine's decode span carries it under these names
                for name, v in stats._asdict().items():
                    self.sow("stats", name, v, reduce_fn=lambda a, b: a + b,
                             init_fn=lambda v=v: jnp.zeros((), v.dtype))
        x = RMSNorm(self.norm_eps, self.dtype, name="norm_f")(x)
        return nn.Dense(self.vocab, use_bias=False, dtype=self.dtype,
                        param_dtype=self.dtype, name="lm_head")(x).astype(
                            jnp.float32)
