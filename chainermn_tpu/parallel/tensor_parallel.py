"""Intra-op tensor parallelism helpers.

Beyond-reference capability (SURVEY.md §2.6: the reference's model
parallelism is graph-partition only; true intra-op TP comes "for free" on a
mesh). The Megatron-style pair:

* **column-parallel** Dense: weight sharded on the output dim; activations
  stay sharded, no collective in forward;
* **row-parallel** Dense: weight sharded on the input dim; forward ends in a
  ``psum`` over the model axis (backward gets the broadcast automatically).

A column→row pair implements a sharded MLP with exactly one all-reduce, and
a QKV-column / out-row pair does the same for attention. These are shard_map
building blocks; under plain ``pjit`` the same layouts fall out of weight
``PartitionSpec``s — both idioms are supported.
"""

from __future__ import annotations

import functools

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.utils import match_vma


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_tp_region(x, axis_name):
    """Megatron's *f* operator: identity forward, psum backward.

    Placed at the entry of every column-parallel region. Each shard's
    backward produces only ITS slice's contribution to the input gradient;
    the full gradient is their sum. Without this, gradients flowing back to
    REPLICATED parameters (embeddings, LayerNorms) are partial and differ
    per shard, silently desynchronizing them from the first optimizer step
    (the row-parallel side needs no twin: psum's transpose is already the
    broadcast)."""
    return x


def _copy_fwd(x, axis_name):
    # a zero-size witness of x's varying axes: custom_vjp checks that the
    # cotangent comes back with the primal's type
    return x, x[..., :0]


def _copy_bwd(axis_name, witness, g):
    return (match_vma(lax.psum(g, axis_name), witness),)


copy_to_tp_region.defvjp(_copy_fwd, _copy_bwd)


class ColumnParallelDense(nn.Module):
    """Dense with output features split over ``axis_name``.

    In-shard features = ``features // axis_size``. Input must be replicated
    (or identically sharded) across the model axis; output is sharded on the
    feature dim. The input rides :func:`copy_to_tp_region`, so gradients
    leaving the TP region are the full cross-shard sum.
    """

    features: int
    axis_name: str
    use_bias: bool = True
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        n = lax.axis_size(self.axis_name)
        assert self.features % n == 0, (
            f"features {self.features} not divisible by axis {n}")
        local = self.features // n
        x = copy_to_tp_region(x, self.axis_name)
        y = nn.Dense(local, use_bias=self.use_bias, dtype=self.dtype)(x)
        return y


class RowParallelDense(nn.Module):
    """Dense with input features split over ``axis_name``; forward psums.

    Input is feature-sharded (the column-parallel output); the result is the
    full matmul, replicated across the model axis.
    """

    features: int
    axis_name: str
    use_bias: bool = True
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        y = nn.Dense(self.features, use_bias=False, dtype=self.dtype)(x)
        y = lax.psum(y, self.axis_name)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros_init(),
                              (self.features,))
            y = y + bias
        return y


class TensorParallelMLP(nn.Module):
    """Column → activation → row: one psum per MLP block."""

    hidden: int
    out: int
    axis_name: str
    act: Callable = nn.gelu
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        h = ColumnParallelDense(self.hidden, self.axis_name,
                                dtype=self.dtype)(x)
        h = self.act(h)
        return RowParallelDense(self.out, self.axis_name,
                                dtype=self.dtype)(h)


def vocab_parallel_cross_entropy(logits, targets, axis_name: str):
    """Cross-entropy over VOCAB-SHARDED logits — the loss-parallel epilogue
    of a column-parallel LM head.

    The full [B, L, V] logits never exist on any device: each shard holds a
    contiguous vocab slice ``[i*Vl, (i+1)*Vl)`` (the layout
    `ColumnParallelDense` produces) and the softmax normalizer, max shift,
    and target logit are assembled with one pmax and two psums of [B, L]
    arrays — communication is O(B·L), not O(B·L·V).

    logits: [..., V_local] (sharded on ``axis_name``); targets: [...] int
    GLOBAL vocab ids (replicated). Returns per-token loss [...], replicated.
    """
    vl = logits.shape[-1]
    lo = lax.axis_index(axis_name) * vl
    logits = logits.astype(jnp.float32)
    # the max shift is gradient-neutral (it cancels in softmax); pmax has
    # no differentiation rule, so route it through a zero-cotangent VJP
    m = pmax_stop_gradient(jnp.max(logits, -1), axis_name)
    z = lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), -1), axis_name)
    local_t = targets - lo
    in_shard = (local_t >= 0) & (local_t < vl)
    safe_t = jnp.clip(local_t, 0, vl - 1)
    tlogit = jnp.take_along_axis(logits, safe_t[..., None], -1)[..., 0]
    tlogit = lax.psum(jnp.where(in_shard, tlogit, 0.0), axis_name)
    return m + jnp.log(z) - tlogit


def pmax_stop_gradient(x, axis_name):
    """lax.pmax treated as a constant by differentiation (no pmax VJP
    exists in JAX): for the logsumexp max shift, which needs none, and
    for metrics computed alongside a differentiated loss. The gradient
    stops BEFORE the collective, so its missing rule is never asked."""
    return lax.pmax(lax.stop_gradient(x), axis_name)
