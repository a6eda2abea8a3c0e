"""Fused LM-head + softmax cross-entropy: logits never touch HBM.

The classic LM loss materializes [B·L, V] f32 logits (2 GB at the bench
shape) and round-trips them ~6× through HBM (head fwd write, CE read,
argmax-metric read, softmax recompute, dlogits write+read) — ~12 GB/step,
measured ~15-18 ms of the 155 ms step (docs/lm_roofline.md §1-2). This
module computes the head matmul and the cross-entropy TOGETHER, flash-
attention style:

* **forward**: grid (row-tile, vocab-tile); each [R, VT] logits tile
  lives only in VMEM; online running max / sum-exp / target-logit /
  argmax accumulate per row. Outputs are O(B·L): lse, target logit,
  argmax. HBM traffic = read h + read W once.
* **backward**: dlogits_ij = (softmax_ij − onehot_ij)·c is rebuilt per
  tile from the forward's lse (the flash trick). Like flash's dq vs
  dk/dv, the two parameter cotangents accumulate across DIFFERENT grid
  dims, so two passes: dh (rows outer, vocab inner — [R, D] scratch) and
  dW (vocab outer, rows inner — [D, VT] scratch). Each pass re-runs the
  head matmul once; matmul FLOPs total 3× the naive head's fwd+bwd 3× —
  identical — while logits HBM traffic disappears.

Numerics: logits accumulate in f32 (MXU native-dtype dots), the
softmax/lse math is f32 throughout — same as the unfused
`optax.softmax_cross_entropy_with_integer_labels` on f32 logits.

Reference analog: none (upstream seq2seq computes full softmax CE);
this is the TPU-native counterpart of the vocab-parallel CE idea applied
to the single-chip memory axis instead of the model-parallel axis.

Measured on this tree: the three kernels run at 93.7% of their compute
roofline in both training cells (``train_fused_ce_roofline``,
PERF_LEDGER.jsonl); fused against unfused throughput: not measured on
this tree. What it buys is MEMORY: the [B·L, V] f32 logits buffer
disappears from the activation footprint. Use it when logits memory is
the binding constraint (big vocab, long L, grad accumulation); the
default losses stay unfused.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.flash_attention import _dimsem, _sds
from chainermn_tpu.utils import match_vma, on_tpu

_NEG = -1e30
# rows-outer passes accumulate across the vocab (innermost) dim only →
# rows can stay 'parallel'; the dW pass accumulates across rows with
# vocab outer, so both its dims must be 'arbitrary'-safe
_DIMSEM_ROWS = _dimsem(("parallel", "arbitrary"))
_DIMSEM_DW = _dimsem(("arbitrary", "arbitrary"))


def _traced(compute):
    """Run ``compute`` under a traced, always-true predicate. The Pallas
    interpreter evaluates the kernel on the caller's values, and under
    shard_map's check_vma its fresh (axis-invariant) scratch then meets
    batch-varying tiles in one primitive; a traced cond keeps the body
    opaque to that check (flash_attention._fa_kernel does the same)."""
    pl.when(pl.program_id(0) >= 0)(compute)


def _dlogits_tile(h_ref, w_ref, y_ref, lse_ref, col0):
    """softmax − onehot for one [R, VT] logits tile, rebuilt from lse."""
    logits = jax.lax.dot_general(
        h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    p = jnp.exp(logits - lse_ref[...])
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    return p - jnp.where(cols == y_ref[...] - col0, 1.0, 0.0)


def _fwd_kernel(h_ref, w_ref, y_ref, lse_ref, tl_ref, am_ref,
                m_acc, s_acc, t_acc, a_acc, *, vt, nv):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_acc[:] = jnp.full_like(m_acc, _NEG)
        s_acc[:] = jnp.zeros_like(s_acc)
        t_acc[:] = jnp.zeros_like(t_acc)
        a_acc[:] = jnp.zeros_like(a_acc)

    @_traced
    def _compute():
        logits = jax.lax.dot_general(
            h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [R, VT]
        m_prev = m_acc[:, :1]
        m_cur = jnp.max(logits, -1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        s_acc[:, :1] = s_acc[:, :1] * alpha + jnp.sum(
            jnp.exp(logits - m_new), -1, keepdims=True)
        m_acc[:, :1] = m_new
        # target logit: the tile holding each row's label contributes it
        y_loc = y_ref[...] - vi * vt                    # [R, 1]
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        hit = cols == y_loc
        t_acc[:, :1] += jnp.sum(jnp.where(hit, logits, 0.0), -1,
                                keepdims=True)
        # running argmax (metric): strictly-greater keeps the FIRST max,
        # matching jnp.argmax tie-breaking
        better = m_cur > m_prev
        # first-match argmax without lax.argmax (Mosaic-safe): the
        # smallest column index attaining the tile max
        is_max = logits == m_cur
        arg_cur = vi * vt + jnp.min(
            jnp.where(is_max, cols, jnp.int32(2 ** 30)), -1, keepdims=True)
        a_acc[:, :1] = jnp.where(better, arg_cur.astype(jnp.float32),
                                 a_acc[:, :1])

    @pl.when(vi == nv - 1)
    def _fin():
        lse_ref[...] = m_acc[:, :1] + jnp.log(s_acc[:, :1])
        tl_ref[...] = t_acc[:, :1]
        am_ref[...] = a_acc[:, :1]


def _dh_kernel(h_ref, w_ref, y_ref, lse_ref, dh_ref, dh_acc, *, vt, nv):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        dh_acc[:] = jnp.zeros_like(dh_acc)

    @_traced
    def _compute():
        dl = _dlogits_tile(h_ref, w_ref, y_ref, lse_ref, vi * vt)
        dh_acc[:] += jax.lax.dot_general(
            dl.astype(w_ref.dtype), w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [R, D]

    @pl.when(vi == nv - 1)
    def _fin():
        dh_ref[...] = dh_acc[:].astype(dh_ref.dtype)


def _dw_kernel(h_ref, w_ref, y_ref, lse_ref, dw_ref, dw_acc, *, vt, nr):
    vi = pl.program_id(0)
    ri = pl.program_id(1)

    @pl.when(ri == 0)
    def _init():
        dw_acc[:] = jnp.zeros_like(dw_acc)

    @_traced
    def _compute():
        dl = _dlogits_tile(h_ref, w_ref, y_ref, lse_ref, vi * vt)
        dw_acc[:] += jax.lax.dot_general(
            h_ref[...], dl.astype(h_ref.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [D, VT]

    @pl.when(ri == nr - 1)
    def _fin():
        dw_ref[...] = dw_acc[:].astype(dw_ref.dtype)


def _pad_rows_to(x, n, fill=0):
    if x.shape[0] == n:
        return x
    pad = [(0, n - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=fill)


def fused_ce_head(h, w, y, block_rows: int = 256, block_v: int = 2048):
    """``mean CE( h @ w , y )`` + argmax accuracy, logits never in HBM.

    h: [N, D] (bf16/f32 hidden states, rows = flattened B·L tokens);
    w: [D, V] head kernel; y: [N] int32 labels in [0, V).
    Returns ``(loss, acc)`` — scalars, differentiable w.r.t. h and w
    (y gets no gradient). Rows are padded internally to the block size;
    padded rows are masked out of both loss and accuracy.

    Under shard_map's varying-axis tracking a REPLICATED head kernel next
    to batch-varying hidden states would fail the kernel's dot with mixed
    vma operands, so ``w`` is pcast to ``h``'s varying axes HERE, outside
    the custom_vjp: autodiff then transposes the pcast into the psum that
    gives ``dW`` the replicated type of the primal ``w`` (the contract
    custom_vjp checks at trace time), exactly as it does for a plain
    ``h @ w``. ``allreduce_grad`` sees an already-reduced gradient and
    only scales it.
    """
    return _ce_head(h, match_vma(w, h), y, block_rows, block_v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ce_head(h, w, y, block_rows, block_v):
    loss, acc, _ = _fwd(h, w, y, block_rows, block_v)
    return loss, acc


def _run_fwd(h, w, y, block_rows, block_v, interpret):
    n, d = h.shape
    v = w.shape[1]
    nr, nv = n // block_rows, v // block_v
    row = lambda r, vi: (r, 0)
    out_row = pl.BlockSpec((block_rows, 1), row, memory_space=pltpu.VMEM)
    lse, tl, am = pl.pallas_call(
        functools.partial(_fwd_kernel, vt=block_v, nv=nv),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((block_rows, d), row, memory_space=pltpu.VMEM),
            pl.BlockSpec((d, block_v), lambda r, vi: (0, vi),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), row,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(out_row, out_row, out_row),
        out_shape=(_sds(h, (n, 1), jnp.float32, w, y),
                   _sds(h, (n, 1), jnp.float32, w, y),
                   _sds(h, (n, 1), jnp.float32, w, y)),
        scratch_shapes=[pltpu.VMEM((block_rows, 128), jnp.float32)] * 4,
        interpret=interpret,
        compiler_params=_DIMSEM_ROWS,
        name="fused_ce_fwd",
    )(h, w, y)
    return lse, tl, am


def _fwd(h, w, y, block_rows, block_v):
    interpret = not on_tpu()
    n0, d = h.shape
    v = w.shape[1]
    if v % block_v:
        raise ValueError(f"vocab {v} must be a multiple of block_v "
                         f"{block_v}")
    n = -(-n0 // block_rows) * block_rows
    hp = _pad_rows_to(h, n)
    # padded labels point at column 0; their rows are masked below
    yp = _pad_rows_to(jnp.asarray(y, jnp.int32).reshape(-1, 1), n)
    lse, tl, am = _run_fwd(hp, w, yp, block_rows, block_v, interpret)
    valid = (jnp.arange(n) < n0)[:, None]
    per_tok = jnp.where(valid, lse - tl, 0.0)
    loss = jnp.sum(per_tok) / n0
    acc = jnp.sum(jnp.where(
        valid, (am == yp.astype(jnp.float32)).astype(jnp.float32),
        0.0)) / n0
    return loss, acc, (hp, w, yp, lse, n0)


def _fwd_rule(h, w, y, block_rows, block_v):
    loss, acc, res = _fwd(h, w, y, block_rows, block_v)
    return (loss, acc), res


def _bwd_rule(block_rows, block_v, res, g):
    dloss = g[0]  # d(acc) is discarded — a metric, not an objective
    hp, w, yp, lse, n0 = res
    interpret = not on_tpu()
    n, d = hp.shape
    v = w.shape[1]
    nr, nv = n // block_rows, v // block_v
    # padded rows must contribute zero: poison their labels to -1 (no
    # onehot hit) AND zero their dl via lse -> +inf (softmax tile = 0)
    valid = (jnp.arange(n) < n0)[:, None]
    lse_b = jnp.where(valid, lse, jnp.float32(3e38))
    yb = jnp.where(valid, yp, -1)

    row = lambda r, vi: (r, 0)
    dh = pl.pallas_call(
        functools.partial(_dh_kernel, vt=block_v, nv=nv),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((block_rows, d), row, memory_space=pltpu.VMEM),
            pl.BlockSpec((d, block_v), lambda r, vi: (0, vi),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), row,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), row, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, d), row,
                               memory_space=pltpu.VMEM),
        out_shape=_sds(hp, (n, d), hp.dtype, w, yb, lse_b),
        scratch_shapes=[pltpu.VMEM((block_rows, d), jnp.float32)],
        interpret=interpret,
        compiler_params=_DIMSEM_ROWS,
        name="fused_ce_dh",
    )(hp, w, yb, lse_b)

    # the dW pass holds a [D, VT] f32 scratch PLUS the [D, VT] weight
    # tile and [R, VT] recompute intermediates — at D=768/VT=2048 that
    # exceeds scoped VMEM in-program; halve its vocab tile independently.
    # The halved tile must still DIVIDE the vocab (a remainder would
    # leave the tail dW columns unwritten — silent gradient corruption);
    # when it doesn't, fall back to block_v itself, which _fwd already
    # validated — correct at a higher VMEM cost
    bv_dw = min(block_v, 1024)
    if v % bv_dw:
        bv_dw = block_v
    nv_dw = v // bv_dw
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, vt=bv_dw, nr=nr),
        grid=(nv_dw, nr),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda vi, r: (r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((d, bv_dw), lambda vi, r: (0, vi),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), lambda vi, r: (r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), lambda vi, r: (r, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((d, bv_dw), lambda vi, r: (0, vi),
                               memory_space=pltpu.VMEM),
        out_shape=_sds(w, (d, v), w.dtype, hp, yb, lse_b),
        scratch_shapes=[pltpu.VMEM((d, bv_dw), jnp.float32)],
        interpret=interpret,
        compiler_params=_DIMSEM_DW,
        name="fused_ce_dw",
    )(hp, w, yb, lse_b)

    c = dloss / n0
    return ((dh[:n0] * c).astype(hp.dtype), (dw * c).astype(w.dtype),
            None)


_ce_head.defvjp(_fwd_rule, _bwd_rule)


def fused_lm_loss(model, params, x, y, train=True, mutable=None,
                  extra_vars=None, rngs=None,
                  block_rows: int = 256, block_v: int = 2048):
    """Drop-in for ``lm_loss_with_aux`` on plain (non-TP-head, non-MoE)
    TransformerLM models: the [B, L, vocab] logits never materialize.
    Step-factory signature — use as ``loss_fn`` in
    ``make_data_parallel_train_step``."""
    if getattr(model, "moe_experts_per_device", 0):
        raise ValueError(
            "fused_lm_loss drops the MoE load-balancing aux (the 'losses' "
            "collection is not made mutable here) — experts would collapse "
            "silently; use lm_loss_with_aux for MoE models")
    if mutable:
        raise ValueError(
            "fused_lm_loss does not thread mutable collections through "
            f"apply (mutable={mutable!r} would be silently dropped); use "
            "lm_loss_with_aux for models with mutable state")
    del train  # TransformerLM has no train-dependent state (no dropout/BN)
    variables = {"params": params, **(extra_vars or {})}
    hidden = model.clone(return_hidden=True).apply(
        variables, x, rngs=rngs)                    # [B, L, D]
    b, l, d = hidden.shape
    w = params["lm_head"]["kernel"].astype(hidden.dtype)
    loss, acc = fused_ce_head(
        hidden.reshape(b * l, d), w,
        jnp.asarray(y, jnp.int32).reshape(-1), block_rows, block_v)
    return loss, (acc, {})
