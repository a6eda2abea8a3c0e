"""Ring attention — sequence/context parallelism over the ICI ring.

Beyond-reference capability (SURVEY.md §2.6: the reference predates sequence
parallelism; §5 specifies this as the TPU-native answer). The sequence axis
is sharded over a mesh axis; each shard holds a query block and rotates the
K/V blocks around the ring with ``lax.ppermute`` (XLA collective-permute over
ICI neighbor links), accumulating attention with the online-softmax
(flash-style) running max/denominator so the full sequence is never
materialized on one chip. Compute of block t overlaps the transfer of block
t+1 thanks to XLA's latency-hiding scheduler.

Causal masking works on block indices: a shard skips score positions whose
global key index exceeds the global query index.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from chainermn_tpu.ops.flash_attention import DEFAULT_BLOCKS
from chainermn_tpu.utils import on_tpu
from jax import lax


def _block_attend(q, k, v, scale, mask):
    """Scores for one (q-block, kv-block) pair + unnormalized accumulators.

    q: [B, Lq, H, D]; k, v: [B, Lk, H, D]; mask: [Lq, Lk] or None.
    Returns (numerator [B, Lq, H, D], rowmax [B, Lq, H], rowsum [B, Lq, H]).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask[None, None, :, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)                          # [B, H, Lq] (may be -inf)
    # exponentiate against a finite shift; fully-masked rows produce zeros
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)                          # [B, H, Lq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    # return the TRUE max (-inf where masked) — the merge needs it
    return o, jnp.transpose(m, (0, 2, 1)), jnp.transpose(l, (0, 2, 1))


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None):
    """Attention over a sequence sharded on ``axis_name``.

    Call inside shard_map with the sequence dimension sharded:
    q, k, v: [B, L_local, H, D] per shard. Returns [B, L_local, H, D].
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    lq = q.shape[1]
    lk = k.shape[1]

    # running accumulators: numerator, rowsum, rowmax — pcast to varying so
    # the fori_loop carry type matches the (varying) per-shard updates
    from chainermn_tpu.utils import match_vma

    acc = match_vma(jnp.zeros(q.shape, jnp.float32), q)
    lsum = match_vma(jnp.zeros(q.shape[:3], jnp.float32), q)  # [B, Lq, H]
    mrun = match_vma(jnp.full(q.shape[:3], -jnp.inf, jnp.float32), q)

    perm = [(i, (i + 1) % n) for i in range(n)]      # ring rotation

    def body(t, carry):
        acc, lsum, mrun, k_cur, v_cur = carry
        src = (my - t) % n                            # whose KV block this is

        if causal:
            # global positions: queries my*lq + iq, keys src*lk + ik
            iq = my * lq + jnp.arange(lq)[:, None]
            ik = src * lk + jnp.arange(lk)[None, :]
            mask = ik <= iq
        else:
            mask = None

        o_t, m_t, l_t = _block_attend(
            q.astype(jnp.float32), k_cur.astype(jnp.float32),
            v_cur.astype(jnp.float32), scale, mask)

        m_new = jnp.maximum(mrun, m_t)
        # rescale old accumulators; exp(-inf - m) == 0 handles the first step
        alpha = jnp.where(jnp.isfinite(mrun), jnp.exp(mrun - m_new), 0.0)
        beta = jnp.where(jnp.isfinite(m_t), jnp.exp(m_t - m_new), 0.0)
        acc = acc * alpha[..., None] + o_t * beta[..., None]
        lsum = lsum * alpha + l_t * beta
        mrun = m_new

        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return acc, lsum, mrun, k_nxt, v_nxt

    acc, lsum, mrun, _, _ = lax.fori_loop(
        0, n, body, (acc, lsum, mrun, k, v))
    out = acc / jnp.maximum(lsum[..., None], 1e-30)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Ring attention with Pallas flash inner kernels.
#
# The XLA ring above materializes each visiting [L_local, L_local] score
# block; this variant runs the fused flash kernels per visiting block, so
# scores never leave VMEM even within a block. The whole ring carries a
# custom VJP because the merge weights depend on the per-block logsumexp,
# which flash_attention's own VJP does not differentiate through — the ring
# must be the custom_vjp boundary, not the block.
#
# Exactness: the forward merges per-block (o, lse) into the GLOBAL softmax
# result; the backward feeds the global lse and dr = Σ_d dO·O into the
# per-block FlashAttention-2 kernels, whose contributions are exactly the
# global-attention partials for that (q-shard, kv-block) pair. dk/dv
# accumulators ride the same ppermute ring as the kv blocks, so after n
# rotations each block arrives home with its full gradient.
# ---------------------------------------------------------------------------


def _ring_blocks(causal, my, src, full_fn, diag_fn, skip_fn):
    """Dispatch one ring step: visiting block fully visible (src < my),
    on the causal diagonal (src == my), or fully masked (src > my)."""
    if not causal:
        return full_fn()
    idx = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
    return lax.switch(idx, [full_fn, diag_fn, skip_fn])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def ring_flash_attention(q, k, v, axis_name: str, causal: bool = False,
                         scale: Optional[float] = None,
                         block_q: int = DEFAULT_BLOCKS[0],
                         block_k: int = DEFAULT_BLOCKS[1],
                         interpret: Optional[bool] = None):
    """`ring_attention` with the Pallas flash kernel as the per-block
    compute. Same calling convention: inside shard_map, q/k/v
    [B, L_local, H, D] sharded on ``axis_name``; returns [B, L_local, H, D].

    Equal shard sizes are required (shard_map guarantees this). Block
    sizes clamp to divisors of L_local like `flash_attention`'s.
    """
    return _ring_flash_fwd(q, k, v, axis_name, causal, scale, block_q,
                           block_k, interpret)[0]


def _to3(x):
    b, l, h, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, l, d)


def _to4(x3, b, h):
    bh, l, d = x3.shape
    return jnp.transpose(x3.reshape(b, h, l, d), (0, 2, 1, 3))


def _ring_flash_fwd(q, k, v, axis_name, causal, scale, block_q, block_k,
                    interpret):
    from chainermn_tpu.ops.flash_attention import _flash_fwd_3d
    from chainermn_tpu.utils import match_vma

    if interpret is None:
        interpret = not on_tpu()
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, l, h, d = q.shape
    assert k.shape == q.shape, "ring shards must be equal-sized"

    q3, k3, v3 = _to3(q), _to3(k), _to3(v)
    fa = functools.partial(_flash_fwd_3d, scale=scale, block_q=block_q,
                           block_k=block_k, interpret=interpret)

    o = match_vma(jnp.zeros(q3.shape, jnp.float32), q3)
    lse = match_vma(jnp.full((b * h, l, 1), -jnp.inf, jnp.float32), q3)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(t, carry):
        o, lse, k_cur, v_cur = carry
        src = (my - t) % n
        o_t, lse_t = _ring_blocks(
            causal, my, src,
            lambda: fa(q3, k_cur, v_cur, causal=False),
            lambda: fa(q3, k_cur, v_cur, causal=True),
            lambda: (match_vma(jnp.zeros(q3.shape, q3.dtype), q3),
                     match_vma(jnp.full((b * h, l, 1), -jnp.inf,
                                        jnp.float32), q3)),
        )
        # streaming (o, lse) merge — weights are exp(lse_* − lse_new)
        lse_new = jnp.logaddexp(lse, lse_t)
        safe = jnp.where(jnp.isfinite(lse_new), lse_new, 0.0)
        w_old = jnp.where(jnp.isfinite(lse), jnp.exp(lse - safe), 0.0)
        w_new = jnp.where(jnp.isfinite(lse_t), jnp.exp(lse_t - safe), 0.0)
        o = o * w_old + o_t.astype(jnp.float32) * w_new
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return o, lse_new, k_nxt, v_nxt

    o, lse, _, _ = lax.fori_loop(0, n, body, (o, lse, k3, v3))
    out = _to4(o.astype(q.dtype), b, h)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, causal, scale, block_q, block_k, interpret,
                    res, g):
    from chainermn_tpu.ops.flash_attention import _flash_bwd_3d
    from chainermn_tpu.utils import match_vma

    q, k, v, out, lse = res
    if interpret is None:
        interpret = not on_tpu()
    sc = scale if scale is not None else q.shape[-1] ** -0.5
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, l, h, d = q.shape

    q3, k3, v3, do3 = _to3(q), _to3(k), _to3(v), _to3(g)
    dr3 = jnp.sum(do3.astype(jnp.float32) * _to3(out).astype(jnp.float32),
                  axis=-1)                                  # [BH, L]
    fb = functools.partial(_flash_bwd_3d, scale=sc, block_q=block_q,
                           block_k=block_k, interpret=interpret)

    zero3 = lambda ref: match_vma(jnp.zeros(ref.shape, jnp.float32), q3)
    dq = zero3(q3)
    dk_acc = zero3(k3)   # rides the ring with its kv block
    dv_acc = zero3(v3)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(t, carry):
        dq, k_cur, v_cur, dk_acc, dv_acc = carry
        src = (my - t) % n
        dqt, dkt, dvt = _ring_blocks(
            causal, my, src,
            lambda: fb(q3, k_cur, v_cur, do3, lse, dr3, causal=False),
            lambda: fb(q3, k_cur, v_cur, do3, lse, dr3, causal=True),
            lambda: (zero3(q3).astype(q3.dtype), zero3(k3).astype(k3.dtype),
                     zero3(v3).astype(v3.dtype)),
        )
        dq = dq + dqt.astype(jnp.float32)
        dk_acc = dk_acc + dkt.astype(jnp.float32)
        dv_acc = dv_acc + dvt.astype(jnp.float32)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = lax.ppermute(dk_acc, axis_name, perm)
        dv_nxt = lax.ppermute(dv_acc, axis_name, perm)
        return dq, k_nxt, v_nxt, dk_nxt, dv_nxt

    dq, _, _, dk_acc, dv_acc = lax.fori_loop(
        0, n, body, (dq, k3, v3, dk_acc, dv_acc))
    return (_to4(dq, b, h).astype(q.dtype),
            _to4(dk_acc, b, h).astype(k.dtype),
            _to4(dv_acc, b, h).astype(v.dtype))


ring_flash_attention.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def local_attention_reference(q, k, v, causal: bool = False,
                              scale: Optional[float] = None):
    """Single-device full attention (the correctness oracle)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        mask = jnp.arange(lk)[None, :] <= jnp.arange(lq)[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
