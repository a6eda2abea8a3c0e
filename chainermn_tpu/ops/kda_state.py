"""Pallas one-token KDA recurrence: the decode step's state update as ONE
in-place pass over the state of the rows that are live.

A decode step advances every live row of a serving grid by one token of
``S ← (I − β k kᵀ) Diag(exp g) S + β k vᵀ``, ``o = Sᵀ q`` per head
(``models/hybrid.py::kda_step``). Written in ``jax.numpy`` the compiler
makes two reduction passes and one write over the float32 state of EVERY
slot — a row that is not live has ``g = 0, β = 0`` and is read and written
back unchanged — where the live rows alone hold a fraction of it (PERF.md
§6, PR 40). Here the state stays in HBM, aliased to the result, and the
grid runs over (live row, block of heads): the list of live rows and their
count are scalar-prefetched and the state block's index comes from the
list, so one grid step copies one live row's heads in, applies the
recurrence head by head in VMEM and copies them out. A step past the count
re-addresses the last live row's block (the pipeline sees the index it
holds and starts no DMA) and does nothing: a dead row's bytes are never
streamed and stay what they were.

The arithmetic is ``kda_step``'s, in its order, in float32 on the vector
unit: multiply and sum over ``d_k``, no matrix unit (it would round the
state's operands). ``exp(g)``, ``k``, ``β·k`` and ``q`` arrive as
``[heads, d_k]`` blocks and are transposed in the kernel, so that a head's
vector lies along the state's rows and broadcasts along its lanes.

:func:`step_kernel_refusal` is the dispatcher's rule
(``models/hybrid.py::kda_decode_step``): which calls the kernel can serve,
by what it can see at trace time; ``kda_step`` serves the others.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.latent_attention import LANES
from chainermn_tpu.ops.page_write import pages_are_partitioned
from chainermn_tpu.utils import on_tpu

__all__ = ["kda_step_fwd", "step_kernel_refusal", "head_block", "PATHS"]

#: the kind under which the dispatcher notes its path
#: (``record_paths(PATHS)``)
PATHS = "state_step"
#: what one grid step may hold of the state in VMEM: a block of heads in
#: and one out, each double-buffered. All 32 heads of a row (2 MB in, 2 MB
#: out) were faster on a v5e than 8 and 16 at every occupancy (ROADMAP S15,
#: PR 37), so the block is as many heads as fit
_STATE_VMEM = 32 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024


def head_block(h: int, dk: int, dv: int) -> int:
    """Heads one grid step takes: all ``h`` if their state fits
    ``_STATE_VMEM`` four times over, else the largest divisor of ``h`` in
    whole sublane tiles (8) that does."""
    fits = lambda hb: 4 * hb * dk * dv * 4 <= _STATE_VMEM
    return max((hb for hb in (*range(8, h, 8), h)
                if h % hb == 0 and fits(hb)), default=h)


def step_kernel_refusal(q, v, state) -> Optional[str]:
    """Why :func:`kda_step_fwd` cannot serve this call, or ``None`` if it
    can: the state is float32 and held by one device, a head's ``[d_k,
    d_v]`` is whole lane tiles, and the program runs on a TPU."""
    dk, dv = q.shape[-1], v.shape[-1]
    if state.dtype != jnp.float32:
        return f"state {state.dtype}, not float32"
    if dk % LANES or dv % LANES:
        return f"d_k {dk}, d_v {dv} are not both multiples of {LANES}"
    if pages_are_partitioned():
        return "state split over several devices"
    if not on_tpu():
        return "not on a TPU"
    return None


def _kernel(rows_ref, n_ref, eg_ref, k_ref, bk_ref, q_ref, v_ref, s_ref,
            o_ref, s_out_ref):
    del rows_ref                    # consumed by the index maps
    i = pl.program_id(0)
    n = n_ref[0]

    @pl.when(i < n)
    def _row():
        # [heads, d_k] -> [d_k, heads]: head h's vector is column h, which
        # broadcasts along the state's lanes
        eg, k, bk, q = (ref[0].T for ref in (eg_ref, k_ref, bk_ref, q_ref))
        for h in range(s_ref.shape[1]):
            col = slice(h, h + 1)
            s = s_ref[0, h] * eg[:, col]
            ks = jnp.sum(k[:, col] * s, axis=0, keepdims=True)
            s = s + bk[:, col] * (v_ref[0, col, :] - ks)
            s_out_ref[0, h] = s
            o_ref[0, col, :] = jnp.sum(q[:, col] * s, axis=0, keepdims=True)

    # no live row at all: every step addresses row 0's last block of heads,
    # which is written back at the end, so it goes out as it came in (its
    # ``o`` is masked with every dead row's)
    @pl.when((n == 0) & (i == 0) & (pl.program_id(1) == 0))
    def _untouched():
        s_out_ref[...] = s_ref[...]


def kda_step_fwd(q, k, v, g, beta, state, live, *,
                 heads: Optional[int] = None):
    """``kda_step`` for the rows that are ``live``. q, k, g ``[B, H, dk]``;
    v ``[B, H, dv]``; beta ``[B, H]``; state ``[B, H, dk, dv]`` float32;
    ``live`` bool ``[B]``; shapes as :func:`step_kernel_refusal` admits
    them. Returns ``(o [B, H, dv]`` float32 — zeros for a row that is not
    live —, the state``)``: a live row's state advanced by its token, any
    other row's bytes untouched; the state is aliased to the result (in
    place under ``jit`` when donated or dead afterwards). ``heads`` a grid
    step (:func:`head_block` by default) divides ``H``."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[-1]
    hb = head_block(h, dk, dv) if heads is None else heads
    if h % hb or (hb % 8 and hb != h):
        raise ValueError(f"{hb} heads a grid step do not tile {h} heads")
    return _step(q, k, v, g, beta, state, live, heads=hb,
                 interpret=not on_tpu())


# jitted for its cache, not for speed: a model's layers call it at one
# shape, and the kernel's unrolled heads are traced and lowered once a
# program, not once a layer (10 s of a decode program's set-up otherwise)
@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _step(q, k, v, g, beta, state, live, *, heads, interpret):
    b, h, dk = q.shape
    dv = v.shape[-1]
    hb, nj = heads, h // heads
    live = jnp.asarray(live, bool)
    filled = jnp.cumsum(live.astype(jnp.int32))
    n = filled[-1:]
    # rows[i]: the i-th live row; past the count, the last live one again
    at = jnp.minimum(jnp.arange(b, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    rows = jnp.minimum(jnp.searchsorted(
        filled, at + 1, side="left", method="compare_all"), b - 1
    ).astype(jnp.int32)
    f32 = lambda a: a.astype(jnp.float32)
    # a step past the count stays on the last live row's LAST block of heads
    block = lambda i, j, rows, n: (rows[i], jnp.where(i < n[0], j, nj - 1))
    vec = pl.BlockSpec((1, hb, dk), lambda *a: block(*a) + (0,))
    out = pl.BlockSpec((1, hb, dv), lambda *a: block(*a) + (0,))
    mat = pl.BlockSpec((1, hb, dk, dv), lambda *a: block(*a) + (0, 0))
    o, state = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nj),
            in_specs=[vec, vec, vec, vec, out, mat],
            out_specs=[out, mat],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operands count the two scalar-prefetched ones: 7 is the state
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="kda_step_fwd",
    )(rows, n, jnp.exp(f32(g)), f32(k), f32(beta)[..., None] * f32(k),
      f32(q), f32(v), state)
    # a row no grid step addressed holds whatever the buffer held
    return jnp.where(live[:, None, None], o, 0.0), state
