"""Pallas flash attention — the fused hot-op kernel.

Reference parity note: the reference's only custom device kernels are CuPy
cast/pack elementwise kernels (SURVEY.md §2.2); XLA already fuses those here.
The kernel worth hand-writing on TPU is blockwise attention: one pass over
K/V tiles in VMEM with online softmax, never materializing the [L, L] score
matrix in HBM. Supports GQA/MQA (index-map KV-head sharing), segment-id
packing, sliding windows, and automatic padding for TPU-illegal lengths.
Usable standalone; `ring_flash_attention`
(chainermn_tpu/parallel/ring_attention.py) runs these kernels as the
per-block inner loop of the sequence-parallel ring.

Layout: [B, L, H, D] → kernel works on [B*H, L, D]. Grid is
(batch*heads, q_blocks, kv_blocks) with the kv dimension innermost; VMEM
scratch (acc, rowmax, rowsum) persists across the kv iteration of one
(bh, q_block) and is finalized on the last kv step. Causal masking compares
global row/col indices and skips fully-masked tiles.

Backward is a pair of Pallas kernels (FlashAttention-2 style): the forward
saves only O and the per-row logsumexp; dq (kv-innermost grid) and dk/dv
(q-innermost grid) rebuild each P tile as exp(S − lse) and accumulate in
VMEM scratch, so the [L, L] score matrix never exists in HBM in either
direction.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.utils import on_tpu

_NEG_INF = -1e30  # finite stand-in: -inf breaks max/exp chains on the VPU

# tuned default tile sizes (v5e, 2026-07-30 sweep — BASELINE.md); clamped
# to legal divisors of L per call, so they are safe for any length. The
# single source of truth: models/parallel wrappers import this.
DEFAULT_BLOCKS = (1024, 1024)


def _dimsem(dims=("parallel", "parallel", "arbitrary")):
    """Grid dims (batch*heads, tile, tile): the first two are independent,
    only the innermost accumulates — declaring this lets Mosaic pipeline
    the HBM block copies across grid steps instead of serializing
    copy→compute."""
    return pltpu.CompilerParams(dimension_semantics=dims)


_DIMSEM = _dimsem()
# the fused backward accumulates dk/dv scratch ACROSS the qi grid dim
# (init at qi==0, flush at qi==nq-1) — a 'parallel' qi would let a
# megacore split it over TensorCores and silently return one core's
# partial sums; only the batch*heads dim is truly independent there
_DIMSEM_FUSED = _dimsem(("parallel", "arbitrary", "arbitrary"))


def _window_cap(block_k: int, window) -> int:
    """Cap block_k near the sliding window: tiles wider than the
    window defeat the band-tile skip (every q row would pay for a full
    k-tile of mostly-masked columns). Applied identically in the forward
    and backward rules so the custom_vjp pair stays consistent."""
    if window is None:
        return block_k
    return min(block_k, max(128, ((window + 127) // 128) * 128))


def _fit_block(block: int, l: int) -> int:
    """Largest divisor of ``l`` that is <= ``block``, preferring
    lane-aligned (multiple-of-128) tiles, then sublane-aligned (8).

    Keeps the tuned defaults usable for any length a caller brings
    (L=384 → 128, L=768 with block 512 → 384) instead of asserting.
    """
    b = min(block, l)
    for align in (128, 8, 1):
        cand = (b // align) * align
        while cand >= align:
            if l % cand == 0:
                return cand
            cand -= align
    return 1


def _padded_len(block: int, l: int) -> int:
    """Length after padding to make a TPU-legal block exist.

    A block is legal when it divides l AND (is a multiple of 8 OR equals
    l). Lengths like 2047 (divisors 89/23) or 100 (divisor 50) admit no
    legal block smaller than l worth using — pad to the next multiple of
    128 (8 for short rows) and mask the tail via segment ids."""
    blk = _fit_block(block, l)
    if blk % 8 == 0 or blk == l:
        return l
    step = 128 if l >= 128 else 8
    return ((l + step - 1) // step) * step


def _causal_live(qi, ki, bq, bk, window=None):
    """Whether tile (qi, ki) intersects the visible band: below the causal
    diagonal and, with a sliding window, within ``window`` positions of
    it (the first q row of the tile must still see the last k column)."""
    live = qi * bq + bq - 1 >= ki * bk
    if window is not None:
        live = jnp.logical_and(live, ki * bk + bk - 1 > qi * bq - window)
    return live


def _tile_scores(q_ref, k_ref, qi, ki, *, scale, causal, bq, bk,
                 qs_ref=None, ks_ref=None, window=None):
    """Scaled and masked score tile S = (Q Kᵀ)·scale (causal, sliding
    window, and/or segment masking).

    Shared by the forward and both backward kernels so masking semantics
    can never desynchronize between them. Segment masking (packed
    sequences) blanks positions whose query and key segment ids differ;
    a sliding window keeps only the last ``window`` positions (causal).
    """
    # native-dtype operands, f32 accumulation: a bf16 model's Q·Kᵀ runs at
    # the MXU's bf16 rate (upcasting first quartered throughput and paid
    # VPU casts); f32 inputs behave exactly as before
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                  # [bq, bk]
    if causal:
        # NOTE(measured 2026-07-31): specializing interior tiles to skip
        # this masking via lax.cond on (qi, ki) regressed the LM bench
        # 96.6k → 84.3k tok/s — Mosaic's traced branch costs more than
        # the iota/compare/select it saves. Keep the mask unconditional.
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep = rows >= cols
        if window is not None:
            keep = jnp.logical_and(keep, rows - cols < window)
        s = jnp.where(keep, s, _NEG_INF)
    if qs_ref is not None:
        s = jnp.where(qs_ref[0] == ks_ref[0], s, _NEG_INF)  # (bq,1)==(1,bk)
    return s


def _masked_exp(s, shift, has_segs):
    """exp(s - shift) with masked entries forced to exactly 0.

    Segment masking can fully mask a row (padding) or a whole tile; there
    ``shift`` (running max or lse) is itself ≈ _NEG_INF and the naive
    exp(s - shift) = exp(0) = 1 (or overflows). Causal-only masking never
    produces such rows (column 0 is always visible), so the select is
    compiled in only when segments are present.
    """
    e = jnp.exp(s - shift)
    if has_segs:
        e = jnp.where(s <= 0.5 * _NEG_INF, 0.0, e)
    return e


def _fa_kernel(*refs, scale, causal, bq, bk, nk, has_segs=False,
               window=None):
    if has_segs:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, lse_ref,
         acc, mrow, lrow) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc, mrow, lrow = refs
        qs_ref = ks_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        mrow[:] = jnp.full_like(mrow, _NEG_INF)
        lrow[:] = jnp.zeros_like(lrow)

    def _compute():
        s = _tile_scores(q_ref, k_ref, qi, ki, scale=scale, causal=causal,
                         bq=bq, bk=bk, qs_ref=qs_ref, ks_ref=ks_ref,
                         window=window)
        m_prev = mrow[:, :1]                       # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = _masked_exp(s, m_new, has_segs)        # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)            # [bq, 1]
        lrow[:, :1] = lrow[:, :1] * alpha + jnp.sum(p, -1, keepdims=True)
        # P cast to V's dtype: bf16 MXU dot with f32 accumulation
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mrow[:, :1] = m_new

    # causal: a tile fully above the diagonal contributes nothing. The
    # predicate must be TRACED even when trivially true: the Pallas
    # interpreter mishandles varying-axes tracking (shard_map check_vma)
    # for ref reads outside a traced cond.
    pl.when(_causal_live(qi, ki, bq, bk, window) if causal
            else ki >= 0)(_compute)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc[:] / jnp.maximum(lrow[:, :1], 1e-30)).astype(
            o_ref.dtype)
        # logsumexp per row — the backward kernels rebuild P = exp(S - lse)
        lse_ref[0] = (mrow[:, :1] +
                      jnp.log(jnp.maximum(lrow[:, :1], 1e-30)))


def _sds(ref, shape, dtype, *more):
    """ShapeDtypeStruct declaring the union of the operands' varying mesh
    axes — required for pallas_call outputs inside shard_map
    (check_vma=True)."""
    vma = frozenset()
    for x in (ref,) + more:
        vma = vma | (getattr(jax.typeof(x), "vma", None) or frozenset())
    return (jax.ShapeDtypeStruct(shape, dtype, vma=vma)
            if vma else jax.ShapeDtypeStruct(shape, dtype))


def _kv_row_map(hq: int, hkv: int):
    """Grid row (over B*Hq) → KV array row (over B*Hkv).

    GQA/MQA share one KV head among ``hq // hkv`` consecutive query heads
    (repeat-interleave convention); the sharing happens in the BlockSpec
    index map, so the repeated KV never exists in HBM."""
    if hq == hkv:
        return lambda b, qi, ki: (b, ki, 0)
    g = hq // hkv
    return lambda b, qi, ki: ((b // hq) * hkv + (b % hq) // g, ki, 0)


def _seg_specs(hq, bq, bk, order_qk=True):
    """BlockSpecs for segment-id operands: q_seg [B, Lq, 1] tiles
    (1, bq, 1); kv_seg [B, 1, Lk] tiles (1, 1, bk) — both minimal legal
    TPU layouts (the block dim of 1 equals the array dim). Grid row b runs
    over B*Hq; segment ids are per batch, hence the ``b // hq``."""
    if order_qk:
        qmap = lambda b, qi, ki: (b // hq, qi, 0)
        kmap = lambda b, qi, ki: (b // hq, 0, ki)
    else:  # (b, ki, qi) grids
        qmap = lambda b, ki, qi: (b // hq, qi, 0)
        kmap = lambda b, ki, qi: (b // hq, 0, ki)
    return (pl.BlockSpec((1, bq, 1), qmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk), kmap, memory_space=pltpu.VMEM))


def _flash_fwd_3d(q, k, v, *, causal, scale, block_q, block_k, interpret,
                  hq=1, hkv=1, segs=None, window=None):
    """q: [B*Hq, Lq, D]; k, v: [B*Hkv, Lk, D] → ([B*Hq, Lq, D],
    lse [B*Hq, Lq, 1]). ``segs``: (q_seg [B, Lq, 1], kv_seg [B, 1, Lk]).

    lse rides a trailing dim of 1: TPU block shapes must have last-two dims
    divisible by (8, 128) OR equal to the array dims, so (1, bq, 1) on a
    [BH, Lq, 1] array is the minimal legal layout — 4 B/row in HBM (the
    earlier 128-lane broadcast moved 128x that)."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    bq = _fit_block(block_q, lq)
    bk = _fit_block(block_k, lk)
    nk = lk // bk
    kv_map = _kv_row_map(hq, hkv)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk,
        has_segs=segs is not None, window=window)
    grid = (bh, lq // bq, nk)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), kv_map, memory_space=pltpu.VMEM),
    ]
    operands = (q, k, v)
    if segs is not None:
        in_specs += list(_seg_specs(hq, bq, bk))
        operands += segs
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), lambda b, qi, ki: (b, qi, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(_sds(q, (bh, lq, d), q.dtype, k, v),
                   _sds(q, (bh, lq, 1), jnp.float32, k, v)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),     # acc
            pltpu.VMEM((bq, 128), jnp.float32),   # running max (col 0)
            pltpu.VMEM((bq, 128), jnp.float32),   # running sum (col 0)
        ],
        interpret=interpret,
        compiler_params=_DIMSEM,
        name="flash_fwd",
    )(*operands)
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels (FlashAttention-2 style): rebuild P from lse per tile,
# never materializing [L, L] in HBM — the memory bound that lets b=64/L=2048
# (and far longer L) train on one chip where the materializing backward
# allocated 8 GB score tensors per block.
# ---------------------------------------------------------------------------

def _fa_bwd_dq_kernel(*refs, scale, causal, bq, bk, nk,
                      has_segs=False, window=None):
    if has_segs:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dr_ref, qs_ref, ks_ref,
         dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dr_ref, dq_ref,
         dq_acc) = refs
        qs_ref = ks_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute():
        s = _tile_scores(q_ref, k_ref, qi, ki, scale=scale, causal=causal,
                         bq=bq, bk=bk, qs_ref=qs_ref, ks_ref=ks_ref,
                         window=window)
        p = _masked_exp(s, lse_ref[0], has_segs)       # [bq, bk]
        # native-dtype MXU dots, f32 accumulation (see _tile_scores)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, bk]
        ds = p * (dp - dr_ref[0]) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # traced-predicate gate even when non-causal — see _fa_kernel
    pl.when(_causal_live(qi, ki, bq, bk, window) if causal
            else ki >= 0)(_compute)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(*refs, scale, causal, bq, bk, nq,
                       has_segs=False, window=None):
    if has_segs:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dr_ref, qs_ref, ks_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dr_ref, dk_ref, dv_ref,
         dk_acc, dv_acc) = refs
        qs_ref = ks_ref = None
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        s = _tile_scores(q_ref, k_ref, qi, ki, scale=scale, causal=causal,
                         bq=bq, bk=bk, qs_ref=qs_ref, ks_ref=ks_ref,
                         window=window)
        p = _masked_exp(s, lse_ref[0], has_segs)       # [bq, bk]
        # native-dtype MXU dots, f32 accumulation (see _tile_scores)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bk, d]
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, bk]
        ds = p * (dp - dr_ref[0]) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bk, d]

    # traced-predicate gate even when non-causal — see _fa_kernel
    pl.when(_causal_live(qi, ki, bq, bk, window) if causal
            else qi >= 0)(_compute)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _fa_bwd_fused_kernel(*refs, scale, causal, bq, bk, nq, nk,
                         has_segs=False, window=None):
    """One-pass backward: dq, dk, dv from a SINGLE rebuild of the score
    tile. The split dq/dkv kernels each recompute S, P and dP — 2 of the
    7 tile dots are pure duplication (plus double HBM reads of q/k/v/do).
    Here dk/dv accumulate across the qi sweep in whole-Lk VMEM scratch
    (f32 [Lk, D] each — 512 KB at L=2048/D=64), flushed on the last grid
    step; dq accumulates per qi exactly like the split kernel. Applicable
    while the scratch fits VMEM (see _FUSED_BWD_SCRATCH_BYTES); the split
    kernels remain the long-L path."""
    if has_segs:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dr_ref, qs_ref, ks_ref,
         dq_ref, dk_ref, dv_ref, dq_acc, dk_all, dv_all) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dr_ref, dq_ref, dk_ref,
         dv_ref, dq_acc, dk_all, dv_all) = refs
        qs_ref = ks_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(jnp.logical_and(qi == 0, ki == 0))
    def _init_kv():
        dk_all[:] = jnp.zeros_like(dk_all)
        dv_all[:] = jnp.zeros_like(dv_all)

    @pl.when(ki == 0)
    def _init_q():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute():
        s = _tile_scores(q_ref, k_ref, qi, ki, scale=scale, causal=causal,
                         bq=bq, bk=bk, qs_ref=qs_ref, ks_ref=ks_ref,
                         window=window)
        p = _masked_exp(s, lse_ref[0], has_segs)       # [bq, bk]
        # native-dtype MXU dots, f32 accumulation (see _tile_scores)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, bk]
        ds = p * (dp - dr_ref[0]) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        sl = pl.dslice(ki * bk, bk)
        dv_all[sl, :] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bk, d]
        dk_all[sl, :] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bk, d]

    # traced-predicate gate even when non-causal — see _fa_kernel
    pl.when(_causal_live(qi, ki, bq, bk, window) if causal
            else ki >= 0)(_compute)

    @pl.when(ki == nk - 1)
    def _finalize_q():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(qi == nq - 1, ki == nk - 1))
    def _finalize_kv():
        dk_ref[0] = dk_all[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_all[:].astype(dv_ref.dtype)


# dk+dv whole-Lk f32 scratch budget for the fused backward (VMEM is
# ~16 MB/core; the [bq, bk] tile intermediates need the rest). Empirical
# v5e boundary (2026-07-31): Lk=4096/D=64 compiles ISOLATED but exceeds
# scoped VMEM by 2.6 MB inside the full LM train step (surrounding
# program raises the pressure), so the gate is the envelope measured
# safe IN-PROGRAM: Lk <= 2048 and 2 MB scratch — both corners verified
# in full 12-layer LM train steps on the chip (Lk=2048 at D=64 AND at
# D=128, the byte-budget boundary). Longer sequences take the split
# dq/dkv kernels.
_FUSED_BWD_SCRATCH_BYTES = 2 * 2 ** 20
_FUSED_BWD_MAX_LK = 2048


def _flash_bwd_slabbed(q, k, v, do, lse, dr, *, causal, scale, block_q,
                       block_k, interpret, hq, hkv, segs, slab):
    """Long-Lk FUSED backward: KV sliced into slabs that fit the fused
    kernel's whole-Lk VMEM scratch (r5). Per slab, causal structure is
    block-wise — q rows before the slab contribute nothing, the diagonal
    region runs with in-slab causal masking, rows after see the whole
    slab unmasked — the ring executor's visiting-block trichotomy
    (parallel/ring_attention.py `_ring_blocks`) applied serially on one
    chip. Every (q, kv) tile pair still pays the fused kernel's 5 dots
    (the split fallback pays 7), so sequences beyond the in-program
    envelope keep the fused backward's arithmetic. dq accumulates in
    f32 across slab contributions; each slab's dk/dv is the f32 sum of
    its diagonal and suffix calls, concatenated along Lk."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    q_seg = kv_seg = None
    if segs is not None:
        q_seg, kv_seg = segs
    dq32 = jnp.zeros((bh, lq, d), jnp.float32)
    dks, dvs = [], []
    for s0 in range(0, lk, slab):
        s1 = min(s0 + slab, lk)
        ks, vs = k[:, s0:s1], v[:, s0:s1]
        kvs = None if kv_seg is None else kv_seg[:, :, s0:s1]
        if causal:
            # diagonal region: q rows [s0, s1) (lq == lk asserted at
            # dispatch), in-slab causal; suffix: q rows [s1, lq) unmasked
            regions = [(s0, s1, True)]
            if s1 < lq:
                regions.append((s1, lq, False))
        else:
            regions = [(0, lq, False)]
        dk_acc = jnp.zeros((bh, s1 - s0, d), jnp.float32)
        dv_acc = jnp.zeros((bh, s1 - s0, d), jnp.float32)
        for r0, r1, diag in regions:
            sub_segs = None
            if q_seg is not None:
                sub_segs = (q_seg[:, r0:r1], kvs)
            dq_p, dk_p, dv_p = _flash_bwd_3d(
                q[:, r0:r1], ks, vs, do[:, r0:r1],
                lse[:, r0:r1], dr[:, r0:r1],
                causal=diag, scale=scale, block_q=block_q,
                block_k=block_k, interpret=interpret, hq=hq, hkv=hkv,
                segs=sub_segs)
            dq32 = dq32.at[:, r0:r1].add(dq_p.astype(jnp.float32))
            dk_acc = dk_acc + dk_p.astype(jnp.float32)
            dv_acc = dv_acc + dv_p.astype(jnp.float32)
        dks.append(dk_acc.astype(k.dtype))
        dvs.append(dv_acc.astype(v.dtype))
    return (dq32.astype(q.dtype), jnp.concatenate(dks, axis=1),
            jnp.concatenate(dvs, axis=1))


def _flash_bwd_3d(q, k, v, do, lse, dr, *, causal, scale, block_q, block_k,
                  interpret, hq=1, hkv=1, segs=None, window=None):
    """q/do: [B*Hq, Lq, D]; k/v: [B*Hkv, Lk, D]; lse/dr: [B*Hq, Lq] →
    (dq [B*Hq], dk, dv [B*Hq — caller reduces query-head groups when
    hkv < hq]). ``segs``: (q_seg [B, Lq, 1], kv_seg [B, 1, Lk])."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    fused_ok = (2 * lk * d * 4 <= _FUSED_BWD_SCRATCH_BYTES
                and lk <= _FUSED_BWD_MAX_LK)
    if not fused_ok and window is None and (not causal or lq == lk):
        # beyond the fused envelope: slab the KV range so each piece
        # fits it, keeping the 5-dot fused kernel (window masking is
        # position-relative and would break on slices — it stays on the
        # split path; causal slabbing needs the self-attention lq == lk
        # alignment)
        slab = min(_FUSED_BWD_MAX_LK,
                   _FUSED_BWD_SCRATCH_BYTES // (8 * d))
        slab -= slab % 128  # lane-aligned; >= 128 keeps legal tiles
        if slab >= 128:
            return _flash_bwd_slabbed(
                q, k, v, do, lse, dr, causal=causal, scale=scale,
                block_q=block_q, block_k=block_k, interpret=interpret,
                hq=hq, hkv=hkv, segs=segs, slab=slab)
    lse = lse.reshape(bh, lq, 1)   # minimal legal TPU block layout
    dr = dr.reshape(bh, lq, 1)
    bq = _fit_block(block_q, lq)
    bk = _fit_block(block_k, lk)
    nq, nk = lq // bq, lk // bk
    kv_map = _kv_row_map(hq, hkv)
    has_segs = segs is not None

    q_spec = pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, bk, d), kv_map, memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, bq, 1), lambda b, qi, ki: (b, qi, 0),
                            memory_space=pltpu.VMEM)

    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    operands = (q, k, v, do, lse, dr)
    if has_segs:
        in_specs += list(_seg_specs(hq, bq, bk))
        operands += segs

    if fused_ok:  # the ONE envelope predicate, computed at dispatch
        dkv_full = pl.BlockSpec((1, lk, d), lambda b, qi, ki: (b, 0, 0),
                                memory_space=pltpu.VMEM)
        return pl.pallas_call(
            functools.partial(_fa_bwd_fused_kernel, scale=scale,
                              causal=causal, bq=bq, bk=bk, nq=nq, nk=nk,
                              has_segs=has_segs, window=window),
            grid=(bh, nq, nk),
            in_specs=in_specs,
            out_specs=(q_spec, dkv_full, dkv_full),
            out_shape=(_sds(q, (bh, lq, d), q.dtype, k, v, do),
                       _sds(k, (bh, lk, d), k.dtype, q, v, do),
                       _sds(v, (bh, lk, d), v.dtype, q, k, do)),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                            pltpu.VMEM((lk, d), jnp.float32),
                            pltpu.VMEM((lk, d), jnp.float32)],
            interpret=interpret,
            compiler_params=_DIMSEM_FUSED,
            name="flash_bwd_fused",
        )(*operands)

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, has_segs=has_segs,
                          window=window),
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=_sds(q, (bh, lq, d), q.dtype, k, v, do),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        compiler_params=_DIMSEM,
        name="flash_bwd_dq",
    )(*operands)

    # dk/dv iterate q innermost; same index maps with (b, ki, qi). Outputs
    # stay per-QUERY-head ([B*Hq] rows) — for GQA the caller sums each
    # query-head group (the transpose of the index-map sharing above).
    q_spec2 = pl.BlockSpec((1, bq, d), lambda b, ki, qi: (b, qi, 0),
                           memory_space=pltpu.VMEM)
    kv_map2 = lambda b, ki, qi: kv_map(b, qi, ki)
    kv_spec2 = pl.BlockSpec((1, bk, d), kv_map2, memory_space=pltpu.VMEM)
    dkv_spec2 = pl.BlockSpec((1, bk, d), lambda b, ki, qi: (b, ki, 0),
                             memory_space=pltpu.VMEM)
    row_spec2 = pl.BlockSpec((1, bq, 1), lambda b, ki, qi: (b, qi, 0),
                             memory_space=pltpu.VMEM)
    in_specs2 = [q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2]
    if has_segs:
        in_specs2 += list(_seg_specs(hq, bq, bk, order_qk=False))
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, has_segs=has_segs,
                          window=window),
        grid=(bh, nk, nq),
        in_specs=in_specs2,
        out_specs=(dkv_spec2, dkv_spec2),
        out_shape=(_sds(k, (bh, lk, d), k.dtype, q, v, do),
                   _sds(v, (bh, lk, d), v.dtype, q, k, do)),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        compiler_params=_DIMSEM,
        name="flash_bwd_dkv",
    )(*operands)
    return dq, dk, dv


def _reference(q, k, v, causal, scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        mask = jnp.arange(lk)[None, :] <= jnp.arange(lq)[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 9, 10, 11))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCKS[0],
                    block_k: int = DEFAULT_BLOCKS[1],
                    interpret: Optional[bool] = None,
                    segment_ids=None, window: Optional[int] = None,
                    bwd_blocks: Optional[Tuple[int, int]] = None,
                    layout: str = "blhd"):
    """Fused blockwise attention. q: [B, Lq, H, D]; k, v: [B, Lk, Hkv, D]
    → [B, Lq, H, D]. Hkv < H is GQA/MQA (H % Hkv == 0, repeat-interleave
    head sharing) — the shared KV is never replicated in HBM; the sharing
    lives in the kernel's block index maps.

    ``layout="bhld"``: q [B, H, Lq, D]; k, v [B, Hkv, Lk, D] →
    [B, H, Lq, D] — the PIVOT-FREE wire format. The kernels natively
    consume [B*H, L, D]; from bhld that is a zero-cost reshape, whereas
    from the default blhd layout every call transposes q/k/v in and the
    output (plus all four gradients) back out — ~15 ms/step of HBM
    copies on the 135M LM (docs/lm_roofline.md §5). A model that keeps
    its attention tensors head-major (projection einsums emit
    [B, H, L, D] directly — XLA folds the permutation into the matmul
    for free, measured 2026-07-31) pays zero layout traffic end to end;
    see ``TransformerLM(qkv_layout="bhld")``.

    ``segment_ids`` enables packed-sequence masking (the TPU-native answer
    to the reference seq2seq's variable-length batching — static shapes,
    many sequences per row): an int32 [B, L] array (self-attention) or a
    (q_seg [B, Lq], kv_seg [B, Lk]) pair; positions attend only within
    their segment (composed with causal). Rows whose segment matches no
    key (e.g. padding marked -1 vs 0-based ids) produce zero output and
    zero gradient.

    ``window`` (requires causal) is sliding-window attention: each query
    attends to its last ``window`` positions only; tiles fully outside
    the band are skipped, so compute scales with L·window instead of L².

    ``interpret=None`` auto-selects: the Pallas interpreter off-TPU (tests),
    the compiled kernel on TPU.

    Default blocks (1024, 1024) measured fastest on v5e with the
    native-dtype MXU + pipelined-DMA kernel (2026-07-30 sweep: 7.3 ms vs
    8.6 ms at (256,512) for the d=64/L=2048 LM shape; 6.6 vs 11.6 ms at
    d=128/L=8192; backward agrees) — see BASELINE.md. Block sizes are
    clamped to the largest divisor of L (lane-aligned where possible), so
    any length works; explicit blocks are only a tuning knob.
    """
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                      segment_ids, window, bwd_blocks, layout)[0]


def _to3(x):
    b, l, h, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, l, d)


def _norm_segs(segment_ids, lq, lk):
    """→ None or kernel-layout (q_seg [B, Lq, 1], kv_seg [B, 1, Lk])."""
    if segment_ids is None:
        return None
    if isinstance(segment_ids, (tuple, list)):
        qs, ks = segment_ids
    else:
        qs = ks = segment_ids
        if lq != lk:
            raise ValueError(
                "a single segment_ids array needs Lq == Lk; pass a "
                "(q_seg, kv_seg) pair for cross-attention")
    return (jnp.asarray(qs, jnp.int32)[:, :, None],
            jnp.asarray(ks, jnp.int32)[:, None, :])


def _pad_rows(x, n):
    return jnp.pad(x, ((0, 0), (0, n)) + ((0, 0),) * (x.ndim - 2))


def _apply_padding(q, k, v, segment_ids, block_q, block_k, batch=None):
    """Pad Lq/Lk to TPU-legal block lengths, masking the tail with
    segment ids (query pad −1, kv pad −2: matches nothing, including each
    other). Returns (q, k, v, effective_segment_ids, lq_pad, lk_pad) with
    the ORIGINAL arrays when no padding is needed. Works on blhd 4D
    arrays or (with ``batch`` given, since dim 0 is then B*H) on the
    kernel-native 3D [B*H, L, D] arrays — dim 1 is L either way."""
    b, lq = (batch if batch is not None else q.shape[0]), q.shape[1]
    lk = k.shape[1]
    lq_p, lk_p = _padded_len(block_q, lq), _padded_len(block_k, lk)
    if lq_p == lq and lk_p == lk:
        return q, k, v, segment_ids, 0, 0
    if segment_ids is None:
        qs, ks = jnp.zeros((b, lq), jnp.int32), jnp.zeros((b, lk), jnp.int32)
    elif isinstance(segment_ids, (tuple, list)):
        qs, ks = segment_ids
    else:
        qs = ks = segment_ids
    qs = jnp.where(_pad_rows(jnp.ones((b, lq), bool), lq_p - lq),
                   _pad_rows(jnp.asarray(qs, jnp.int32), lq_p - lq), -1)
    ks = jnp.where(_pad_rows(jnp.ones((b, lk), bool), lk_p - lk),
                   _pad_rows(jnp.asarray(ks, jnp.int32), lk_p - lk), -2)
    q = _pad_rows(q, lq_p - lq)
    k = _pad_rows(k, lk_p - lk)
    v = _pad_rows(v, lk_p - lk)
    return q, k, v, (qs, ks), lq_p - lq, lk_p - lk


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               segment_ids=None, window=None, bwd_blocks=None,
               layout="blhd"):
    if window is not None and not causal:
        raise ValueError("window (sliding-window attention) requires "
                         "causal=True")
    if layout not in ("blhd", "bhld"):
        raise ValueError(f"layout must be 'blhd' or 'bhld', got "
                         f"{layout!r}")
    block_k = _window_cap(block_k, window)
    if interpret is None:
        interpret = not on_tpu()
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if layout == "bhld":
        # head-major wire format: [B, H, L, D] ↔ [B*H, L, D] is a free
        # reshape — the transpose copies of the blhd path never happen
        b, h, lq, d = q.shape
        hk = k.shape[1]
        if h % hk:
            raise ValueError(
                f"query heads ({h}) must be a multiple of kv heads ({hk})")
        qp, kp, vp, segs_eff, _, _ = _apply_padding(
            q.reshape(b * h, lq, d), k.reshape(b * hk, -1, d),
            v.reshape(b * hk, -1, d), segment_ids, block_q, block_k,
            batch=b)
        segs = _norm_segs(segs_eff, qp.shape[1], kp.shape[1])
        out3, lse3 = _flash_fwd_3d(
            qp, kp, vp,
            causal=causal, scale=scale, block_q=block_q, block_k=block_k,
            interpret=interpret, hq=h, hkv=hk, segs=segs, window=window)
        out = out3.reshape(b, h, qp.shape[1], d)[:, :, :lq]
        return out, (q, k, v, out, lse3, segment_ids)
    b, lq, h, d = q.shape
    hk = k.shape[2]
    if h % hk:
        raise ValueError(
            f"query heads ({h}) must be a multiple of kv heads ({hk})")
    qp, kp, vp, segs_eff, _, _ = _apply_padding(
        q, k, v, segment_ids, block_q, block_k)
    segs = _norm_segs(segs_eff, qp.shape[1], kp.shape[1])
    out3, lse3 = _flash_fwd_3d(
        _to3(qp), _to3(kp), _to3(vp),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret, hq=h, hkv=hk, segs=segs, window=window)
    out = jnp.transpose(out3.reshape(b, h, qp.shape[1], d),
                        (0, 2, 1, 3))[:, :lq]
    return out, (q, k, v, out, lse3, segment_ids)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window,
               bwd_blocks, layout, res, g):
    # blockwise Pallas backward: P is rebuilt per tile from the forward's
    # logsumexp; [L, L] never touches HBM (the materializing fallback
    # allocated 8 GB f32 score tensors at b=64/L=2048/h=8)
    q, k, v, out, lse3, segment_ids = res
    if bwd_blocks is not None:
        # the backward kernels' VMEM/compute balance differs from the
        # forward's (4 live [bq, bk] f32 intermediates vs 2); let callers
        # tune them independently
        block_q, block_k = bwd_blocks
    if interpret is None:
        interpret = not on_tpu()
    block_k = _window_cap(block_k, window)
    sc = scale if scale is not None else q.shape[-1] ** -0.5
    if layout == "bhld":
        # head-major: reshapes only, no transposes anywhere in backward
        b, h, lq, d = q.shape
        lk, hk = k.shape[2], k.shape[1]
        qp, kp, vp, segs_eff, pq, pk = _apply_padding(
            q.reshape(b * h, lq, d), k.reshape(b * hk, lk, d),
            v.reshape(b * hk, lk, d), segment_ids, block_q, block_k,
            batch=b)
        lq_p, lk_p = lq + pq, lk + pk
        if lse3.shape[1] != lq_p:
            raise ValueError(
                f"bwd_blocks pad Lq to {lq_p} but the forward's lse is "
                f"{lse3.shape[1]} long; pick bwd blocks with the same "
                "padded length (block-size multiples of the forward's)")
        segs = _norm_segs(segs_eff, lq_p, lk_p)
        g3 = g.reshape(b * h, lq, d)
        gp = _pad_rows(g3, pq) if pq else g3
        # D_i = Σ_d dO_i · O_i — rowwise, already head-major: no pivot
        dr3 = jnp.sum(g3.astype(jnp.float32)
                      * out.reshape(b * h, lq, d).astype(jnp.float32),
                      axis=-1)
        if pq:
            dr3 = _pad_rows(dr3, pq)
        dq3, dk3, dv3 = _flash_bwd_3d(
            qp, kp, vp, gp, lse3, dr3,
            causal=causal, scale=sc, block_q=block_q, block_k=block_k,
            interpret=interpret, hq=h, hkv=hk, segs=segs, window=window)
        if hk < h:
            grp = h // hk
            dk3 = dk3.reshape(b * hk, grp, lk_p, d).sum(1)
            dv3 = dv3.reshape(b * hk, grp, lk_p, d).sum(1)
        dsegs = jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, jax.dtypes.float0), segment_ids)
        return (dq3[:, :lq].reshape(b, h, lq, d),
                dk3[:, :lk].reshape(b, hk, lk, d),
                dv3[:, :lk].reshape(b, hk, lk, d), dsegs)
    b, lq, h, d = q.shape
    lk, hk = k.shape[1], k.shape[2]
    qp, kp, vp, segs_eff, pq, pk = _apply_padding(
        q, k, v, segment_ids, block_q, block_k)
    lq_p, lk_p = lq + pq, lk + pk
    if lse3.shape[1] != lq_p:
        raise ValueError(
            f"bwd_blocks pad Lq to {lq_p} but the forward's lse is "
            f"{lse3.shape[1]} long; pick bwd blocks with the same padded "
            "length (block-size multiples of the forward's)")
    segs = _norm_segs(segs_eff, lq_p, lk_p)
    gp = _pad_rows(g, pq) if pq else g
    # D_i = Σ_d dO_i · O_i — rowwise, cheap in XLA, f32 for stability
    dr = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dr3 = jnp.pad(jnp.transpose(dr, (0, 2, 1)).reshape(b * h, lq),
                  ((0, 0), (0, pq)))
    dq3, dk3, dv3 = _flash_bwd_3d(
        _to3(qp), _to3(kp), _to3(vp), _to3(gp), lse3, dr3,
        causal=causal, scale=sc, block_q=block_q, block_k=block_k,
        interpret=interpret, hq=h, hkv=hk, segs=segs, window=window)
    if hk < h:
        # transpose of the index-map head sharing: sum each query-head group
        grp = h // hk
        dk3 = dk3.reshape(b * hk, grp, lk_p, d).sum(1)
        dv3 = dv3.reshape(b * hk, grp, lk_p, d).sum(1)
    back = lambda x3, hh, lp, l: jnp.transpose(
        x3.reshape(b, hh, lp, d), (0, 2, 1, 3))[:, :l]
    dsegs = jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, jax.dtypes.float0), segment_ids)
    return (back(dq3, h, lq_p, lq), back(dk3, hk, lk_p, lk),
            back(dv3, hk, lk_p, lk), dsegs)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
