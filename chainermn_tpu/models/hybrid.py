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
  them to per-head keys and values, decode uses the absorbed form. The
  query is full-rank or low-rank with its own norm (``q_rank``), the output
  gate is optional (``mla_gate``), the rotary frequencies plain or YaRN's
  (``rope_scaling``). With ``mla_block`` set the page is read in blocks of
  columns under an online softmax (:func:`latent_chunk_attention`,
  :func:`latent_decode_attention`): a chunk of queries at any cursor
  attends the filled page plus itself with no score array over the page,
  and a decode step reads the columns the live rows have filled, not
  ``max_len`` — what 8k-32k prompts need.

* ``"gqa"`` and ``"swa"`` — grouped-query attention over K/V leaves
  (:class:`GQAMixer`, ``ops/kv_attention.py``): over a PAGE of ``max_len``
  columns, and over a RING of ``window`` columns in which a query sees the
  last ``window`` positions. The sizes that differ BY KIND are the model's
  fields (``gqa_heads`` / ``swa_heads`` query heads over ``n_kv_heads``
  shared K/V heads, the rotary share, theta and scaling of each, the
  window); a chunk of queries at any cursor attends the filled columns in
  blocks, a decode step reads what the live rows have filled.

and one FEED-FORWARD of ``"dense"`` (SwiGLU) or ``"moe"`` (this chip's share
of a routed expert layer, ``parallel/expert_share.py``, plus a shared expert).

The residual path is the plain sum (``hc_mult`` 1) or manifold-constrained
hyper-connections (mHC, arXiv:2512.24880; :class:`HyperConnection`): the
state is ``n = hc_mult`` streams a token, ``X ∈ R^{n×d}``, and around each
sub-layer ``F`` three maps computed from the state itself — ``H_pre`` mixes
the streams into ``F``'s input, ``H_post`` spreads ``F``'s output over them,
``H_res`` (doubly stochastic, by Sinkhorn rounds) mixes the streams among
themselves: ``X' = H_res X + H_postᵀ ⊗ F(norm(H_pre X))``.

Serving contract (``serving/state_cache.py`` reads ``declares_cache``): with
``decode=True`` the ``cache`` collection holds, slot-major, whatever the
layers declare — a recurrent state and a convolution tail per KDA layer, a
latent page per MLA layer, a K/V page per ``"gqa"`` layer, a K/V ring per
``"swa"`` layer — and one cursor vector ``idx`` at the root.
``__call__(tokens [B, L], lengths=[B], live=[B])`` advances row ``b`` by
``lengths[b]`` tokens (right-padded rows: a recurrence integrates padding
unless told not to, so positions at or past the length get ``β = 0``,
``α = 1`` and leave the state exactly as it was) and leaves rows that are
not ``live`` untouched apart from page columns past their cursor. With
``pos_offset`` and ``slots`` (blocked latent pages only) the call's rows are
rows ``slots`` of a cache that holds more rows than the call has: a chunk
cohort against the whole grid's pages, where they lie.

With ``n_mtp`` 1 the model carries one multi-token-prediction module
(:class:`MTPModule`, DeepSeek-V3 report §2.2) beside its layers: its own
norms, a ``2d → d`` projection of ``[norm(h_i) ; norm(Emb(t_{i+1}))]``, one
block of the expert kind over its OWN latent page, the main model's
embedding and head. It is run by a call of its own (``hidden=``), never
inside the main call, and the ``cache`` collection then also holds, per
slot, the ``draft`` a self-drafting engine keeps between rounds
(``serving/state_cache.py``). With ``absorbed=True`` a call's ``L`` tokens
are decode positions: every row writes ``L`` latents at its cursor and the
``L`` queries take the absorbed form against one read of the page.

What a leaf of that collection may do: a POSITIONAL leaf
(``HybridLM.positional_leaves``: the latent page ``ckv``, the K/V pages
``k`` and ``v``; axis 1 the position) is addressed by the cursor like K/V
rows, so a call may start at any cursor (``pos``) and a prompt may arrive
in chunks; a WINDOW leaf (``window_leaves``: the K/V rings ``k_win`` and
``v_win``; axis 1 ``window`` columns) is addressed by the cursor ``mod
window``, takes chunks too and wraps by nature; a RECURRENT leaf (every
other one but ``idx``: KDA's ``state`` and ``conv``) is the sum of its
history, starts from what the slot holds and cannot be rewound,
re-windowed or written at a column. ``idx`` and ``draft`` are per-slot
scalars, neither page nor state.
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Mapping, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from chainermn_tpu.ops import kda_state, kv_attention, latent_attention
from chainermn_tpu.ops.kv_attention import (column_blocks as _blocks,
                                            write_window as _write_window)
from chainermn_tpu.parallel.expert_share import HeldExperts, RouteStats

__all__ = ["GQAMixer", "HybridLM", "HybridBlock", "HyperConnection",
           "KDAMixer", "MLAMixer", "MTPModule", "RMSNorm", "SwiGLU", "kda_chunk",
           "kda_decode_step", "kda_step",
           "latent_chunk_attention", "latent_decode_attention",
           "layer_pattern", "sinkhorn", "yarn_inv_freq", "yarn_mscale"]

_HI = jax.lax.Precision.HIGHEST
KDA_CHUNK = 64
_SUB = 16        # sub-block inside a chunk: keeps every exponent <= 0
#: page columns of one row the blocked decode step's ``jax.numpy`` LOOP reads
#: per iteration (``latent_decode_attention`` where it keeps the loop; the
#: kernel sizes its own tile, ``ops/latent_attention.py::
#: decode_column_tile``): one query a row makes a block cheap to score, and
#: an iteration pays the carried accumulators' row out and in whatever the
#: block's width, so it is wide to keep the loop short
DECODE_BLOCK = 4096


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


def kda_decode_step(q, k, v, g, beta, state, live):
    """The decode step's token of the recurrence: :func:`kda_step`'s
    arguments and ``live [B]``, the rows that take a token (any other row
    comes with ``g = 0, beta = 0`` and keeps its state in both forms).

    Two forms, chosen here at trace time by what the call shows
    (``ops/kda_state.py::step_kernel_refusal``; the choice is noted for
    whoever traces the program, ``record_paths("state_step")``, as
    ``"kernel"`` or ``"xla:<reason>"``): on a TPU, with a float32 state
    that one device holds and ``d_k``, ``d_v`` whole lane tiles, ONE Pallas
    kernel a call (``kda_step_fwd``) that visits the live rows' state only,
    reads each block once and writes it once in place, and returns zeros as
    a dead row's ``o`` (nobody reads it); else :func:`kda_step`, which
    streams every row's state through two reductions and a write. Same
    operands and order of operations in both."""
    refusal = kda_state.step_kernel_refusal(q, v, state)
    latent_attention.note_path(
        "kernel" if refusal is None else f"xla:{refusal}", kda_state.PATHS)
    if refusal is None:
        return kda_state.kda_step_fwd(q, k, v, g, beta, state, live)
    return kda_step(q, k, v, g, beta, state)


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
                one = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state)
                # a full forward of one token differentiates through it
                o, state = (kda_decode_step(*one, real[:, 0]) if self.decode
                            else kda_step(*one))
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

def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: ``0.1·mscale·ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(d: int, theta: float, scaling: Mapping[str, Any]):
    """The ``d/2`` rotary frequencies under YaRN (arXiv:2309.00071, as
    DeepSeek-V2 publishes it): pair ``i`` turns by ``theta^(-2i/d)`` where
    it makes more than ``beta_fast`` rotations over the original context,
    by that over ``factor`` where it makes fewer than ``beta_slow``, and by
    a linear blend of the two between (the ramp runs over pair indices)."""
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (d * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(scaling.get("beta_slow", 1))), d - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(d // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * i / d)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rope_interleaved(x, positions, theta, inv_freq=None, mscale=1.0):
    """Rotate adjacent pairs ``(x_{2i}, x_{2i+1})`` of the last axis by
    ``positions · theta^{-2i/d}`` (or by ``inv_freq``, with cos and sin
    scaled by ``mscale``). x ``[B, L, ..., d]``, positions ``[B, L]``."""
    d = x.shape[-1]
    freqs = (theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
             if inv_freq is None else inv_freq)
    ang = positions.astype(jnp.float32)[..., None] * freqs     # [B, L, d/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.reshape(x.shape)


def latent_chunk_attention(q_nope, q_rope, page, w_kvb, pos, scale, block,
                           slots=None, valid=None):
    """Causal attention of a chunk of queries over a latent page, EXPANDED
    block by block. ``q_nope [B, C, H, dn]``, ``q_rope [B, C, H, dr]``: the
    queries at positions ``pos[b] + 0..C-1``, the first ``valid[b]`` of
    them real (all of them without ``valid``); row ``slots[b]`` (``b``
    itself without ``slots``) of ``page [N, T, >= r + dr]`` holds ``[c |
    k_r]`` (and maybe padding) for every column up to those positions (the
    chunk's own included); ``w_kvb [r, H, dn + dv]``. Each block of columns
    is re-expanded to per-head keys and values (640 flop a query-key pair
    and head against the absorbed form's 2,176) and folded into a running
    softmax. Returns ``([B, C, H, dv]`` float32, the page``)``.

    Two forms, chosen here at trace time by what the call shows
    (``ops/latent_attention.py::chunk_kernel_refusal``; the choice is noted
    for whoever traces the program, ``record_paths``): on a TPU, with
    lane-aligned widths and a page that one device holds, ONE Pallas kernel
    a call, which keeps a block's keys, values, scores and probabilities in
    VMEM, stops a row at ``pos + valid``, skips what lies above a query
    tile's diagonal and leaves the query tiles past ``valid`` zero; else
    the ``jax.numpy`` loop below over blocks of ``block`` columns, whose
    largest score array is ``[B, H, C, block]`` whatever the page's length
    and which stops at the last column a query sees. In the loop the page
    rides the carry and the caller keeps what comes out, so that a page
    just written is read where it lies (one that the loop only closed over
    stayed live beside it, and the compiler copied all of it a call)."""
    b, c, h, dn = q_nope.shape
    r = w_kvb.shape[0]
    dv = w_kvb.shape[-1] - dn
    refusal = latent_attention.chunk_kernel_refusal(q_nope, q_rope, page,
                                                    w_kvb)
    latent_attention.note_path(
        "kernel" if refusal is None else f"loop:{refusal}")
    if refusal is None:
        return latent_attention.latent_chunk_fwd(
            q_nope, q_rope, page, w_kvb, pos,
            jnp.full((b,), c, jnp.int32) if valid is None else valid,
            jnp.arange(b) if slots is None else slots, scale), page
    qpos = pos[:, None] + jnp.arange(c)[None]                   # [B, C]
    block, n_blocks, take = _blocks(
        page, jnp.max(pos) + c, block,
        jnp.arange(b) if slots is None else slots)

    def body(j, carry):
        page, m, l, acc = carry
        blk, col, own = take(page, j)
        kv = jnp.einsum("btr,rhe->bthe", blk[..., :r], w_kvb)
        s = (jnp.einsum("bqhe,bkhe->bhqk", q_nope, kv[..., :dn],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhe,bke->bhqk", q_rope,
                          blk[..., r:r + q_rope.shape[-1]],
                          preferred_element_type=jnp.float32)) * scale
        seen = (col[None, None] <= qpos[:, :, None]) & own       # [B, C, blk]
        s = jnp.where(seen[:, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bhqk,bkhe->bhqe", p.astype(kv.dtype), kv[..., dn:],
            preferred_element_type=jnp.float32)
        return page, m_new, l, acc

    # column 0 is seen by every query, so ``m`` is finite after block 0
    init = (page, jnp.full((b, h, c), -jnp.inf, jnp.float32),
            jnp.zeros((b, h, c), jnp.float32),
            jnp.zeros((b, h, c, dv), jnp.float32))
    page, _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    return jnp.moveaxis(acc / l[..., None], 1, 2), page


def latent_decode_attention(q_cat, page, pos, live, scale, r,
                            block=DECODE_BLOCK, offs=None):
    """One query a row over its latent page, ABSORBED, block by block.
    ``q_cat [B, H, r + dr]`` (the no-rope query already through
    ``W_kvb``'s key half, beside the rotary query); row ``b`` sees columns
    ``<= pos[b]``. ONE loop over the (row, block) pairs that hold a column a
    LIVE row has filled, each a plain product of one row's block ``[block,
    r + dr]`` with that row's heads: a step reads what is cached, row by
    row, not the page's capacity (a row that is not live is not visited and
    gets zeros; nobody reads it). The block is the left operand of the
    scores and the right one of the values, so it is read as it lies. (A
    product batched over the rows, ``bhc,btc->bht``, made the compiler
    re-lay the whole page with its columns minor around the loop: a copy of
    every page a step.) Returns ``(``the attended latents ``[B, H, r]``
    float32, the page``)``, the page through the loop's carry as in
    :func:`latent_chunk_attention`.

    With ``offs [H]`` int32 (a tuple of Python ints) the ``H`` rows of
    ``q_cat`` are several queries' heads side by side, query-major, and
    "head" ``i`` belongs to the query at position ``pos[b] + offs[i]``: a
    few queries at consecutive positions against ONE read of the row's
    blocks (the self-drafting round's two, ``serving/state_cache.py``).

    Two forms, chosen here at trace time by what the call shows
    (``ops/latent_attention.py::decode_kernel_refusal``; the choice is noted
    for whoever traces the program, ``record_paths``): on a TPU, with a
    lane-aligned page width and rank, query-heads in eights and a page that
    one device holds, ONE Pallas kernel a call
    (``latent_decode_fwd``), whose grid is (row, block of columns): the
    row's running ``m``, ``l`` and ``acc`` stay in VMEM across its blocks,
    the ``[heads, block]`` score tile never leaves it, the page is an input
    read where it lies, and a block past the row's fill is neither copied
    nor computed — the column tile from the page's capacity and ``H``
    (``decode_column_tile``), ``block`` unused. Else the ``jax.numpy`` loop
    below over blocks of ``min(block, capacity)`` columns, which pays every
    visit the block out of the carried page, the row of the ``[B, H, r]``
    accumulator out and in, and a ``[block, H]`` float32 score array
    through HBM (PERF.md §6, PR 35). Same operands, roundings and mask in
    both; rows that are not live get zeros in both."""
    b, h, w = q_cat.shape
    t = page.shape[1]
    refusal = latent_attention.decode_kernel_refusal(q_cat, page, r)
    latent_attention.note_path(
        "kernel" if refusal is None else f"loop:{refusal}")
    if refusal is None:
        return latent_attention.latent_decode_fwd(
            q_cat, page, pos, live, scale, r, offs=offs), page
    block = min(block, t)
    if offs is None:
        n_of = jnp.where(live, pos // block + 1, 0)     # blocks a row reads
    else:
        n_of = jnp.where(live, (pos + max(offs)) // block + 1, 0)
        offs = jnp.asarray(offs, jnp.int32)
    ends = jnp.cumsum(n_of)

    def body(i, carry):
        page, m, l, acc = carry
        row = jnp.minimum(jnp.searchsorted(ends, i, side="right"), b - 1)
        j = i - (ends[row] - n_of[row])
        s0 = jnp.minimum(j * block, t - block)
        blk = jax.lax.dynamic_slice(page, (row, s0, 0), (1, block, w))[0]
        at = lambda a: jax.lax.dynamic_index_in_dim(a, row, 0, False)
        s = jnp.dot(blk, at(q_cat).T,
                    preferred_element_type=jnp.float32) * scale   # [blk, H]
        col = s0 + jnp.arange(block)
        if offs is None:
            seen = ((col <= at(pos)) & (col >= j * block))[:, None]
        else:
            seen = ((col[:, None] <= at(pos) + offs[None])
                    & (col >= j * block)[:, None])
        s = jnp.where(seen, s, -jnp.inf)
        m_new = jnp.maximum(at(m), s.max(0))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(at(m) - m_new)
        put = lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v, row, 0)
        return (page, put(m, m_new), put(l, alpha * at(l) + p.sum(0)),
                put(acc, alpha[:, None] * at(acc) + jnp.dot(
                    p.T.astype(blk.dtype), blk[:, :r],
                    preferred_element_type=jnp.float32)))

    # a row's block 0 holds column 0, which the row sees: ``m`` is finite
    # from its first visit on
    init = (page, jnp.full((b, h), -jnp.inf, jnp.float32),
            jnp.zeros((b, h), jnp.float32),
            jnp.zeros((b, h, r), jnp.float32))
    page, _, l, acc = jax.lax.fori_loop(0, ends[-1], body, init)
    return jnp.where(l[..., None] > 0, acc / l[..., None], 0.0), page


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
    q_rank: Optional[int] = None     # None: one full-rank query projection
    gate: bool = True                # the head-wise output gate
    rope_scaling: Optional[Mapping[str, Any]] = None    # YaRN's keys
    block: int = 0                   # 0: score the whole page in one piece

    @nn.compact
    def __call__(self, x, pos, lengths=None, live=None, slots=None,
                 absorbed=False):
        """``slots [B]`` (blocked mode only): the cache's rows this call's
        rows live in, where the cache holds more rows than the call has (a
        chunk cohort against the whole grid's pages). ``absorbed`` (blocked
        pages, ``decode=True``): the ``L`` tokens of a row are a few decode
        positions, not a chunk of a prompt — every row writes its ``L``
        latents at ``pos ..`` and the queries take the absorbed form
        against one read of the page."""
        b, l, d = x.shape
        h, dn, dr, dv, r = (self.n_heads, self.d_nope, self.d_rope,
                            self.d_v, self.kv_rank)
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                         param_dtype=self.dtype, name=name)
        positions = pos[:, None] + jnp.arange(l)[None]
        scale = (dn + dr) ** -0.5
        inv_freq, mscale = None, 1.0
        if self.rope_scaling is not None:
            rs = dict(self.rope_scaling)
            if rs.get("type", "yarn") != "yarn":
                raise ValueError(f"rope_scaling type {rs['type']!r}: only "
                                 "'yarn' is implemented")
            inv_freq = yarn_inv_freq(dr, self.rope_theta, rs)
            all_dim = rs.get("mscale_all_dim", 0)
            mscale = (yarn_mscale(rs["factor"], rs.get("mscale", 1))
                      / yarn_mscale(rs["factor"], all_dim))
            if all_dim:
                scale *= yarn_mscale(rs["factor"], all_dim) ** 2
        rope = lambda a: rope_interleaved(
            a, positions, self.rope_theta, inv_freq, mscale).astype(
                self.dtype)
        if self.q_rank is None:
            q = dense(h * (dn + dr), "q_proj")(x)
        else:
            q = dense(h * (dn + dr), "qb_proj")(RMSNorm(
                self.eps, self.dtype, name="q_norm")(
                    dense(self.q_rank, "qa_proj")(x)))
        q = q.reshape(b, l, h, dn + dr)
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:])
        kva = dense(r + dr, "kva_proj")(x)
        c = RMSNorm(self.eps, self.dtype, name="c_norm")(kva[..., :r])
        k_r = rope(kva[..., r:])
        ckv = jnp.concatenate([c, k_r], -1)                    # [B, L, r+dr]
        w_kvb = self.param("kvb_proj", nn.initializers.lecun_normal(),
                           (r, h * (dn + dv)), self.dtype)
        w_kvb = w_kvb.reshape(r, h, dn + dv)
        if self.gate:
            gate = jax.nn.sigmoid(dense(h, "g_proj")(x).astype(jnp.float32))
        if self.decode:
            # read in blocks, the page keeps its rows whole lane tiles wide
            # (576 -> 640): a last axis that is no multiple of 128 the chip
            # stores with the COLUMNS minor, and every row written into such
            # a page re-lays all of it, twice (found compiling for the chip)
            width = -(-(r + dr) // 128) * 128 if self.block else r + dr
            page_v = self.variable("cache", "ckv", jnp.zeros,
                                   (b, self.max_len, width), self.dtype)
            if width > r + dr:
                ckv = jnp.pad(ckv, ((0, 0), (0, 0), (0, width - r - dr)))
        if slots is not None and not (l > 1 and self.block and self.decode):
            raise ValueError("slots address the pages of a blocked chunk "
                             "call (mla_block > 0, decode=True, L > 1)")
        if absorbed and l > 1:
            if not (self.block and self.decode) or slots is not None:
                raise ValueError("several absorbed queries a row need "
                                 "blocked pages (mla_block > 0), decode=True "
                                 "and no slots")
            with jax.named_scope("mla_absorbed"):
                # rows pos .. pos+L-1 of every slot in one scatter: what a
                # row does not keep (a rejected draft's latent, a row that
                # is not live) lies at or beyond its fill
                page = page_v.value.at[
                    jnp.arange(b)[:, None], positions].set(
                        ckv.astype(page_v.value.dtype), mode="drop")
                q_lat = jnp.einsum("blhe,rhe->blhr", q_nope,
                                   w_kvb[..., :dn]).astype(self.dtype)
                q_cat = jnp.concatenate([q_lat, q_rope], -1)
                q_cat = jnp.pad(q_cat, ((0, 0), (0, 0), (0, 0),
                                        (0, page.shape[-1] - r - dr)))
                o_lat, page = latent_decode_attention(
                    q_cat.reshape(b, l * h, -1), page, pos,
                    jnp.ones((b,), bool) if live is None else live, scale, r,
                    offs=tuple(j for j in range(l) for _ in range(h)))
                page_v.value = page
                o = jnp.einsum("blhr,rhe->blhe",
                               o_lat.reshape(b, l, h, r).astype(self.dtype),
                               w_kvb[..., dn:],
                               preferred_element_type=jnp.float32)
        elif l > 1 and self.block:
            # a chunk at any cursor: its latents go into the page at
            # [pos, pos + length), then the chunk attends the page
            with jax.named_scope("mla_chunk"):
                page = ckv
                if self.decode:
                    n = jnp.full((b,), l, jnp.int32) if lengths is None \
                        else lengths
                    if live is not None:
                        n = jnp.where(live, n, 0)
                    page = _write_window(
                        page_v.value, ckv, pos, n,
                        jnp.arange(b) if slots is None else slots)
                o, page = latent_chunk_attention(
                    q_nope, q_rope, page, w_kvb, pos, scale, self.block,
                    slots, n if self.decode else None)
                if self.decode:
                    page_v.value = page
        elif l > 1:
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
                q_lat = jnp.einsum("bhe,rhe->bhr", q_nope[:, 0],
                                   w_kvb[..., :dn]).astype(self.dtype)
                q_cat = jnp.concatenate([q_lat, q_rope[:, 0]], -1)
                if self.block:
                    q_cat = jnp.pad(q_cat, ((0, 0), (0, 0),
                                            (0, page.shape[-1] - r - dr)))
                    o_lat, page = latent_decode_attention(
                        q_cat, page, pos,
                        jnp.ones((b,), bool) if live is None else live,
                        scale, r)
                    page_v.value = page
                else:
                    page_v.value = page
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
        if self.gate:
            o = o * gate[..., None]
        o = o.astype(self.dtype).reshape(b, l, h * dv)
        return dense(d, "o_proj")(o)


# ---------------------------------------------------------------------------
# grouped-query attention over K/V leaves: a page, or a ring of the window
# ---------------------------------------------------------------------------

def rope_halves(x, positions, inv_freq, mscale=1.0):
    """Rotate the FIRST ``2 · len(inv_freq)`` values of the last axis, the
    first half of them against the second (``x_i`` with ``x_{i + n}``), by
    ``positions · inv_freq`` with cos and sin scaled by ``mscale``; the
    values behind them pass through. x ``[B, L, H, d]``, positions ``[B,
    L]``."""
    n = inv_freq.shape[0]
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    x32 = x.astype(jnp.float32)
    x1, x2, rest = x32[..., :n], x32[..., n:2 * n], x32[..., 2 * n:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


class GQAMixer(nn.Module):
    """``n_heads`` query heads over ``n_kv_heads`` keys and values of
    ``d_head``, causal, over FLAT K/V leaves (``ops/kv_attention.py``): a
    PAGE of ``max_len`` columns (``window`` 0; leaves ``k``, ``v``) or a RING
    of ``window`` columns in which a query sees the last ``window`` positions
    (leaves ``k_win``, ``v_win``). Rotary on the first ``rotary`` share of a
    head, half against half, plain or YaRN's (``rope_scaling``: its keys,
    cos and sin scaled by ``attention_factor``, ``0.1 ln factor + 1`` where
    it is not given); with ``gate`` one sigmoid gate a HEAD from the mixer's
    input on the attended values. No bias, no q/k norm.

    ``__call__(x [B, L, d], pos, lengths, live, slots)``: ``L > 1`` is a
    chunk of a prompt at the rows' cursors (the page gets ``[pos, pos +
    length)``, the ring the chunk's last ``min(length, window)`` rows at
    ``position mod window``; with ``slots`` the call's rows are rows
    ``slots`` of leaves that hold more); ``L == 1`` a decode step. A row
    that is not live leaves its leaves as they were.

    A chunk's attention (scopes ``gqa_chunk``, ``swa_chunk``, with the
    page's and the ring's writes) goes through ``kv_attention``'s two
    dispatchers, which choose at trace time by what the call shows — widths,
    dtype, placement, platform; no model name, no flag: ONE Pallas kernel
    for both kinds of leaf (``kv_chunk_fwd``: scores in VMEM, the work
    following each row's cursor and ``lengths``, so the queries past a row's
    length come back zero) or the ``jax.numpy`` bodies, and note which for
    whoever traces the program (``record_paths``; the serving step puts it
    on ``engine.admit`` as ``chunk_attention``). The call without a cache
    (``decode=False``: the chunk is the page, before an empty ring) takes the
    same dispatchers and the same rule."""
    n_heads: int
    n_kv_heads: int
    d_head: int
    rope_theta: float
    max_len: int
    rotary: float = 1.0
    rope_scaling: Optional[Mapping[str, Any]] = None
    window: int = 0
    gate: bool = False
    dtype: Any = jnp.float32
    decode: bool = False

    def _rotation(self):
        rot = int(self.d_head * self.rotary) // 2 * 2
        if self.rope_scaling is None:
            return (self.rope_theta ** (
                -jnp.arange(rot // 2, dtype=jnp.float32) * 2.0 / rot), 1.0)
        rs = dict(self.rope_scaling)
        kind = rs.get("rope_type", rs.get("type", "yarn"))
        if kind != "yarn":
            raise ValueError(f"rope_scaling type {kind!r}: only 'yarn' is "
                             "implemented")
        return (yarn_inv_freq(rot, self.rope_theta, rs),
                rs.get("attention_factor") or yarn_mscale(rs["factor"]))

    @nn.compact
    def __call__(self, x, pos, lengths=None, live=None, slots=None):
        b, l, d = x.shape
        h, n_kv, dh = self.n_heads, self.n_kv_heads, self.d_head
        if h % n_kv:
            raise ValueError(f"{h} query heads over {n_kv} K/V heads")
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                         param_dtype=self.dtype, name=name)
        positions = pos[:, None] + jnp.arange(l)[None]
        inv_freq, mscale = self._rotation()
        rope = lambda a, n: rope_halves(
            a.reshape(b, l, n, dh), positions, inv_freq, mscale).astype(
                self.dtype)
        q = rope(dense(h * dh, "q_proj")(x), h)
        k = rope(dense(n_kv * dh, "k_proj")(x), n_kv).reshape(b, l, n_kv * dh)
        v = dense(n_kv * dh, "v_proj")(x)
        if self.gate:
            gate = jax.nn.sigmoid(dense(h, "g_proj")(x).astype(jnp.float32))
        n = jnp.full((b,), l, jnp.int32) if lengths is None else lengths
        if live is not None:
            n = jnp.where(live, n, 0)
        ring = bool(self.window)
        names = ("k_win", "v_win") if ring else ("k", "v")
        if self.decode:
            cols = self.window if ring else self.max_len
            k_v, v_v = (self.variable("cache", name, jnp.zeros,
                                      (b, cols, n_kv * dh), self.dtype)
                        for name in names)
            k_leaf, v_leaf = k_v.value, v_v.value
        elif l == 1:
            raise ValueError("a one-token call needs decode=True")
        elif ring:      # no cache: an empty ring before the chunk
            k_leaf = v_leaf = jnp.zeros((b, self.window, n_kv * dh),
                                        self.dtype)
        else:           # no cache: the chunk is the page
            k_leaf, v_leaf = k, v
        if slots is not None and not (l > 1 and self.decode):
            raise ValueError("slots address the leaves of a chunk call "
                             "(decode=True, L > 1)")
        scale = dh ** -0.5
        if l > 1 and ring:
            with jax.named_scope("swa_chunk"):
                o = kv_attention.ring_chunk_attention(
                    q, k, v, k_leaf, v_leaf, pos, slots, scale, n)
                if self.decode:
                    k_leaf, v_leaf = (kv_attention.write_ring(
                        leaf, new, pos, n, slots)
                        for leaf, new in ((k_leaf, k), (v_leaf, v)))
        elif l > 1:
            with jax.named_scope("gqa_chunk"):
                rows = jnp.arange(b) if slots is None else slots
                if self.decode:
                    k_leaf, v_leaf = (_write_window(leaf, new, pos, n, rows)
                                      for leaf, new in ((k_leaf, k),
                                                        (v_leaf, v)))
                o, k_leaf, v_leaf = kv_attention.page_chunk_attention(
                    q, k_leaf, v_leaf, pos, rows, scale, n)
        else:
            alive = n > 0
            with jax.named_scope("swa_decode" if ring else "gqa_decode"):
                if ring:
                    k_leaf, v_leaf = (kv_attention.write_ring(
                        leaf, new, pos, n)
                        for leaf, new in ((k_leaf, k), (v_leaf, v)))
                    o = kv_attention.ring_decode_attention(
                        q[:, 0], k_leaf, v_leaf, pos, scale)
                else:
                    at = (jnp.arange(b), pos)

                    def put(leaf, new):     # a row that is not live keeps
                        # the column at its cursor too
                        old = leaf.at[at].get(mode="clip")
                        return leaf.at[at].set(jnp.where(
                            alive[:, None], new[:, 0].astype(leaf.dtype),
                            old), mode="drop")

                    o, k_leaf, v_leaf = kv_attention.page_decode_attention(
                        q[:, 0], put(k_leaf, k), put(v_leaf, v), pos, alive,
                        scale)
                o = o[:, None]
        if self.decode:
            k_v.value, v_v.value = k_leaf, v_leaf
        if self.gate:
            o = o * gate[..., None]
        o = o.astype(self.dtype).reshape(b, l, h * dh)
        return dense(d, "o_proj")(o)


# ---------------------------------------------------------------------------
# hyper-connections
# ---------------------------------------------------------------------------

def sinkhorn(logits, iters: int, eps: float):
    """``iters`` rounds of row-then-column normalisation of ``exp(logits)``
    (``[..., n, n]``), ``eps`` in each divisor: towards the doubly
    stochastic matrices, whose products keep a signal's mean."""
    m = jnp.exp(logits)

    def one(m, _):
        m = m / (m.sum(-1, keepdims=True) + eps)
        return m / (m.sum(-2, keepdims=True) + eps), None

    return jax.lax.scan(one, m, None, length=iters)[0]


class HyperConnection(nn.Module):
    """The three maps of one sub-layer, from the residual state ``X [B, L,
    n, d]`` itself, in float32: ``x̃ = RMSNorm(vec(X))`` (no learned
    scale), ``H̃ = α·(x̃ Φ) + b`` for each of pre ``[n]``, post ``[n]`` and
    res ``[n, n]`` (one ``nd × (2n + n²)`` matrix), ``H_pre = σ(H̃_pre)``,
    ``H_post = 2σ(H̃_post)``, ``H_res = sinkhorn(clamp(H̃_res))``."""
    n: int
    iters: int = 20
    eps: float = 1e-6
    clamp: float = 30.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        n = self.n
        b, l, _, d = x.shape
        phi = self.param("phi", nn.initializers.lecun_normal(),
                         (n * d, 2 * n + n * n), self.dtype)
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,),
                           jnp.float32)
        b_pre = self.param("b_pre", nn.initializers.zeros, (n,), jnp.float32)
        b_post = self.param("b_post", nn.initializers.zeros, (n,),
                            jnp.float32)
        b_res = self.param("b_res", lambda *_: 4.0 * jnp.eye(n), (n, n),
                           jnp.float32)
        x32 = x.reshape(b, l, n * d).astype(jnp.float32)
        xt = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), -1, keepdims=True) + self.norm_eps)
        t = jnp.matmul(xt, phi.astype(jnp.float32), precision=_HI)
        h_pre = jax.nn.sigmoid(alpha[0] * t[..., :n] + b_pre)
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * t[..., n:2 * n] + b_post)
        h_res = alpha[2] * t[..., 2 * n:].reshape(b, l, n, n) + b_res
        h_res = sinkhorn(jnp.clip(h_res, -self.clamp, self.clamp),
                         self.iters, self.eps)
        return h_pre, h_post, h_res


# ---------------------------------------------------------------------------
# block and model
# ---------------------------------------------------------------------------

#: what a decode call of a model with K/V layers sows beside ``RouteStats``,
#: under these names (``HybridLM._read_stats``)
AttnStats = collections.namedtuple("AttnStats", [
    "attn_rows_live", "attn_rows_wrapped", "attn_page_columns",
    "attn_ring_columns", "attn_fill_columns"])


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

    def _mix(self, y, pos, lengths, live, slots, absorbed):
        c = self.cfg
        y = RMSNorm(c.norm_eps, c.dtype, name="norm_mix")(y)
        if self.mixer == "kda":
            if slots is not None or absorbed:
                raise ValueError("a recurrent state is not addressed by "
                                 "slot or by position: it has no chunk call "
                                 "and no several-position decode call")
            return KDAMixer(c.n_heads, c.d_head, c.d_head, conv=c.conv_kernel,
                            lower_bound=c.kda_lower_bound, eps=c.norm_eps,
                            dtype=c.dtype, decode=self.decode,
                            name="kda")(y, lengths, live)
        if self.mixer == "mla":
            return MLAMixer(c.n_heads, c.d_nope, c.d_rope, c.d_head,
                            c.kv_rank, c.rope_theta, c.max_len,
                            eps=c.norm_eps, dtype=c.dtype, decode=self.decode,
                            q_rank=c.q_rank, gate=c.mla_gate,
                            rope_scaling=c.rope_scaling, block=c.mla_block,
                            name="mla")(y, pos, lengths, live, slots,
                                        absorbed)
        if self.mixer not in ("gqa", "swa"):
            raise ValueError(f"unknown mixer kind {self.mixer!r}: one of "
                             "'kda', 'mla', 'gqa', 'swa'")
        if absorbed:
            raise ValueError("several decode positions a row against one "
                             "read are written for latent pages only")
        # the sizes that differ BY KIND: the page's and the ring's
        swa = self.mixer == "swa"
        heads, theta = ((c.swa_heads, c.swa_theta) if swa
                        else (c.gqa_heads, c.gqa_theta))
        if not (heads and theta and c.n_kv_heads and (c.window or not swa)):
            raise ValueError(
                f"a {self.mixer!r} layer needs the model's n_kv_heads, "
                f"{self.mixer}_heads and {self.mixer}_theta"
                + (" and its window" if swa else ""))
        return GQAMixer(
            heads, c.n_kv_heads, c.d_head, theta, c.max_len,
            rotary=c.swa_rotary if swa else c.gqa_rotary,
            rope_scaling=c.swa_scaling if swa else c.gqa_scaling,
            window=c.window if swa else 0, gate=c.attn_gate,
            dtype=c.dtype, decode=self.decode, name=self.mixer)(
                y, pos, lengths, live, slots)

    def _feed(self, y, lengths, live):
        c = self.cfg
        y = RMSNorm(c.norm_eps, c.dtype, name="norm_ffn")(y)
        if self.ffn == "dense":
            return SwiGLU(c.d_ff, dtype=c.dtype, name="ffn")(y), None
        b, l, d = y.shape
        routes = (live[:, None]
                  & (jnp.arange(l)[None] < lengths[:, None])).reshape(-1)
        flat = y.reshape(b * l, d)
        routed, stats = HeldExperts(
            c.n_experts, c.held_lo, c.held_hi, c.d_expert, c.top_k,
            c.n_group, c.topk_group, c.routed_scale, c.norm_topk_prob,
            dtype=c.dtype, name="moe")(flat, routes)
        shared = SwiGLU(c.d_shared, dtype=c.dtype, name="shared")(flat)
        return (routed + shared).reshape(b, l, d), stats

    def _around(self, x, name, f):
        """One sub-layer ``f`` on the residual state: the plain sum, or the
        hyper-connection's three maps around it."""
        c = self.cfg
        if c.hc_mult == 1:
            y, stats = f(x)
            return x + y, stats
        with jax.named_scope("mhc_mix"):
            h_pre, h_post, h_res = HyperConnection(
                c.hc_mult, c.hc_sinkhorn_iters, c.hc_eps, c.hc_clamp,
                c.norm_eps, c.dtype, name=name)(x)
            x32 = x.astype(jnp.float32)
            u = jnp.einsum("bln,blnd->bld", h_pre, x32).astype(c.dtype)
        y, stats = f(u)
        with jax.named_scope("mhc_mix"):
            x = (jnp.einsum("blij,bljd->blid", h_res, x32)
                 + h_post[..., None] * y.astype(jnp.float32)[:, :, None])
        return x.astype(c.dtype), stats

    @nn.compact
    def __call__(self, x, pos, lengths, live, slots=None, absorbed=False):
        x, _ = self._around(
            x, "hc_mix",
            lambda y: (self._mix(y, pos, lengths, live, slots, absorbed),
                       None))
        return self._around(x, "hc_ffn",
                            lambda y: self._feed(y, lengths, live))


class MTPModule(nn.Module):
    """One multi-token-prediction module (DeepSeek-V3 report §2.2), without
    the embedding and the head, which are the main model's: for the main
    hidden state ``h_i`` (the last layer's output BEFORE the final norm) and
    the embedding of the token ``t_{i+1}`` that follows,

    ``u = W_eh [RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))]``, ``h'_i =
    Block(u)`` at position ``i`` — one block of the expert kind: latent
    attention over the module's OWN page, this chip's share of its own
    routed experts, the shared expert — and ``RMSNorm_out(h'_i)`` is what the
    main head turns into the logits of ``t_{i+2}``."""
    cfg: Any                     # HybridLM.dims()
    decode: bool = False

    @nn.compact
    def __call__(self, hidden, emb_next, pos, lengths, live, absorbed=False):
        c = self.cfg
        norm = lambda name: RMSNorm(c.norm_eps, c.dtype, name=name)
        u = nn.Dense(c.d_model, use_bias=False, dtype=c.dtype,
                     param_dtype=c.dtype, name="eh_proj")(
            jnp.concatenate([norm("norm_h")(hidden),
                             norm("norm_e")(emb_next)], -1))
        x, stats = HybridBlock("mla", "moe", c._replace(hc_mult=1),
                               decode=self.decode, name="block")(
            u, pos, lengths, live, None, absorbed)
        return norm("norm_out")(x), stats


class HybridLM(nn.Module):
    """See the module docstring. ``pattern`` is one ``(mixer, ffn)`` pair a
    layer (:func:`layer_pattern` makes the KDA/MLA one; any tuple of pairs
    over ``"kda"``, ``"mla"``, ``"gqa"``, ``"swa"`` will do, an unknown
    mixer kind raises); the expert fields describe the routed
    layers: ``n_experts`` routed over, ``held_lo:held_hi`` held here.

    ``n_mtp`` 1 adds one :class:`MTPModule` (``mtp_0``; 0 creates no
    parameter and no page). The module is run by a call of its own:
    ``__call__(next_tokens, pos_offset=positions, hidden=h, ...)`` returns
    the DRAFT logits — of the token two places on — for the main hidden
    states ``h`` (what a main call hands back beside its logits under
    ``return_hidden``) and the tokens that follow them, reading and writing
    the module's own latent page at those positions and nothing else of the
    cache. ``tok_emb`` and ``lm_head`` are the main model's in both calls:
    one leaf each."""
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
    hc_mult: int = 1                 # residual streams; 1: the plain sum
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: float = 30.0
    q_rank: Optional[int] = None     # MLA's low-rank query; None: full rank
    mla_gate: bool = True
    rope_scaling: Optional[Mapping[str, Any]] = None     # YaRN's keys
    mla_block: int = 0               # page columns a block; 0: one piece
    n_mtp: int = 0                   # multi-token-prediction modules (0, 1)
    # the "gqa" (K/V page) and "swa" (K/V ring) mixers; what differs by kind
    # is given by kind, and a pattern that names a kind gives its sizes
    n_kv_heads: int = 0
    gqa_heads: int = 0
    swa_heads: int = 0
    gqa_rotary: float = 1.0          # share of a head that is rotated
    swa_rotary: float = 1.0
    gqa_theta: Optional[float] = None
    swa_theta: Optional[float] = None
    gqa_scaling: Optional[Mapping[str, Any]] = None     # YaRN's keys
    swa_scaling: Optional[Mapping[str, Any]] = None
    window: int = 0                  # positions a "swa" layer sees and keeps
    attn_gate: bool = False          # one output gate a head on both kinds
    dtype: Any = jnp.float32
    decode: bool = False

    #: serving/kv_cache.py: the pages are what the ``cache`` collection
    #: declares (recurrent state, convolution tail, latent page, K/V page,
    #: K/V ring), not one K/V layout a model
    declares_cache = True
    #: serving/state_cache.py: the declared leaves a cursor addresses like
    #: K/V rows (axis 1 is the position, ``max_len`` long) ...
    positional_leaves = ("ckv", "k", "v")
    #: ... the ones it addresses ``mod`` their length (axis 1 is a ring of
    #: ``window`` columns); any other but ``idx`` and ``draft`` is a
    #: recurrence
    window_leaves = ("k_win", "v_win")

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

    def _sow(self, stats, prefix=""):
        # the serving step returns the collection with the tokens, and the
        # engine's decode span carries it under these names
        for name, v in stats._asdict().items():
            self.sow("stats", prefix + name, v, reduce_fn=lambda a, b: a + b,
                     init_fn=lambda v=v: jnp.zeros((), v.dtype))

    def _read_stats(self, pos, live, decode_step):
        """What the K/V layers read in a decode step, beside what they had
        to: per call the live rows, those whose cursor has passed the window
        (their rings have wrapped), the columns read from pages (the visits
        of ``kv_attention.page_decode_attention``'s loop) and from rings
        (whole, every row) over all layers — 0 for a chunk call — and the
        columns ONE capacity-long page read up to each live row's fill would
        give."""
        count = lambda a: a.sum(dtype=jnp.int32)
        zero = jnp.zeros((), jnp.int32)
        layers = collections.Counter(m for m, _ in self.pattern)
        return AttnStats(
            attn_rows_live=count(live),
            attn_rows_wrapped=count(live & (pos >= self.window))
            if self.window else zero,
            attn_page_columns=layers["gqa"] * kv_attention.decode_columns(
                pos, live, self.max_len) if decode_step else zero,
            attn_ring_columns=jnp.int32(
                layers["swa"] * pos.shape[0] * self.window * decode_step),
            attn_fill_columns=count(jnp.where(live, pos + 1, 0)))

    def _draft(self, emb, head, hidden, tokens, pos, lengths, live, absorbed,
               at):
        """The module's call: draft logits ``[B, L, vocab]`` (``[B, vocab]``
        at the positions ``at [B]``)."""
        with jax.named_scope("mtp_draft"):
            x, stats = MTPModule(self.dims(), decode=self.decode,
                                 name="mtp_0")(hidden, emb(tokens), pos,
                                               lengths, live, absorbed)
            if self.decode:
                self._sow(stats, "mtp_")
            if at is not None:
                x = jnp.take_along_axis(x, at[:, None, None], axis=1)[:, 0]
            return head(x).astype(jnp.float32)

    @nn.compact
    def __call__(self, tokens, pos_offset=None, lengths=None, live=None,
                 slots=None, *, hidden=None, absorbed=False,
                 return_hidden=False, at=None):
        """``slots [B]`` (with ``pos_offset``, models of blocked latent
        pages only): row ``b`` of the call is row ``slots[b]`` of a cache
        that holds more rows than the call has — a chunk cohort run against
        the whole grid's pages where they lie, no copy of a page in or out.
        A slot past the cache's rows is a padding row: it writes nothing.

        ``absorbed``: the ``L`` tokens are decode positions (``MLAMixer``);
        ``return_hidden``: ``(logits, the last layer's output before the
        final norm)``; ``at [B]``: logits of those positions alone, ``[B,
        vocab]``; ``hidden [B, L, d]``: the call is the MTP module's (class
        docstring) and ``tokens`` are the tokens that follow."""
        b, l = tokens.shape
        if lengths is None:
            lengths = jnp.full((b,), l, jnp.int32)
        if live is None:
            live = jnp.ones((b,), bool)
        if slots is not None and (pos_offset is None or not self.decode):
            raise ValueError("slots need decode=True and the rows' cursors "
                             "as pos_offset")
        if self.n_mtp not in (0, 1):
            raise ValueError("one multi-token-prediction module at most "
                             f"(n_mtp {self.n_mtp}): deeper drafting is not "
                             "built")
        emb = nn.Embed(self.vocab, self.d_model, dtype=self.dtype,
                       param_dtype=self.dtype, name="tok_emb")
        head = nn.Dense(self.vocab, use_bias=False, dtype=self.dtype,
                        param_dtype=self.dtype, name="lm_head")
        if hidden is not None:
            if not self.n_mtp or slots is not None:
                raise ValueError("the module's call needs n_mtp 1 and takes "
                                 "no slots")
            if self.decode and pos_offset is None:
                raise ValueError("the module's call takes its positions as "
                                 "pos_offset: it does not move the cursor")
            pos = (jnp.zeros((b,), jnp.int32) if pos_offset is None else
                   jnp.broadcast_to(jnp.asarray(pos_offset, jnp.int32), (b,)))
            return self._draft(emb, head, hidden, tokens, pos, lengths, live,
                               absorbed, at)
        if self.decode:
            idx = self.variable("cache", "idx", jnp.zeros, (b,), jnp.int32)
            pos = idx.value if pos_offset is None else jnp.broadcast_to(
                jnp.asarray(pos_offset, jnp.int32), (b,))
            if self.n_mtp:
                # the draft a slot holds between rounds: the serving
                # program's to read and write, the model's to declare
                self.variable("cache", "draft", jnp.zeros, (b,), jnp.int32)
        else:
            pos = jnp.zeros((b,), jnp.int32)
        x = emb(tokens)
        if self.hc_mult > 1:        # every stream starts as the embedding
            x = jnp.broadcast_to(x[:, :, None],
                                 (b, l, self.hc_mult, self.d_model))
        stats = RouteStats.zero()
        dims = self.dims()
        for i, (mixer, ffn) in enumerate(self.pattern):
            x, st = HybridBlock(mixer, ffn, dims, decode=self.decode,
                                name=f"block_{i}")(x, pos, lengths, live,
                                                   slots, absorbed)
            if st is not None:
                stats = stats + st
        if self.decode:
            moved = pos + jnp.where(live, lengths, 0).astype(jnp.int32)
            idx.value = moved if slots is None else idx.value.at[slots].set(
                moved, mode="drop")
            if self.n_experts:
                self._sow(stats)
            if {"gqa", "swa"} & {m for m, _ in self.pattern}:
                self._sow(self._read_stats(pos, live, decode_step=l == 1))
        if self.hc_mult > 1:        # and the streams are summed at the end
            x = x.astype(jnp.float32).sum(2).astype(self.dtype)
        if self.n_mtp and self.is_initializing():
            # the module's leaves and its page exist from the model's init
            self._draft(emb, head, x, tokens, pos, lengths, live, False,
                        None)
        h = x
        if at is not None:
            x = jnp.take_along_axis(x, at[:, None, None], axis=1)[:, 0]
        x = RMSNorm(self.norm_eps, self.dtype, name="norm_f")(x)
        logits = head(x).astype(jnp.float32)
        return (logits, h) if return_hidden else logits

    def self_draft_refusal(self) -> Optional[str]:
        """Why a serving step cannot run this model's self-drafted rounds
        (``serving/state_cache.py``), or None where it can: the model, not
        the step, knows what its module and its pages take."""
        if not self.n_mtp:
            return "it carries no multi-token-prediction module (n_mtp 1)"
        if not self.mla_block:
            return ("two positions a slot against one read of the latent "
                    "page need blocked pages (mla_block > 0)")
        return None
