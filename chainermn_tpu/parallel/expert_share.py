"""One chip's share of a routed expert layer, every token kept.

``parallel/expert_parallel.py`` is the Switch/GShard TRAINING layer: top-1/2
routing, a capacity factor that drops overflow, an all-to-all. This module
is the serving-side counterpart the large sparse models need: the layer is
told which experts it holds (``held_lo:held_hi`` of ``n_experts``), routes
every token over ALL experts with the published rule, and computes the part
of the result its own experts give — the pairs routed to absent experts are
left out of the sum (they are another chip's part; on one chip nothing
stands in for that chip or for the exchange). No capacity: the pairs that
land here are sorted by expert into whole tiles and run through one grouped
product (``ops/grouped_swiglu.py``), sized for the worst case (every pair on
one expert) so the program is one trace whatever the routing, while the
kernel's work follows the actual counts.

Routing is DeepSeek-V3's sigmoid group-limited top-k with an expert bias:
scores ``s = sigmoid(W_r x)`` in f32; the choice is made on ``s + b`` — the
experts fall into ``n_group`` groups, a group's score is the sum of its two
best, the ``topk_group`` best groups stay, and the ``top_k`` best experts
among them are chosen; the weights are ``s`` (without ``b``) over the chosen,
normalised to sum to 1, times ``routed_scale``.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from chainermn_tpu.ops.grouped_swiglu import (NARROW_TILE, WIDE_TILE,
                                              grouped_swiglu)

__all__ = ["HeldExperts", "RouteStats", "group_limited_topk",
           "held_expert_ffn", "sort_pairs_by_expert"]


class RouteStats(NamedTuple):
    """Counts of one layer's routing, scalars computed on the device (int32
    but the mean). Summed over layers and steps they are what the serving
    spans carry, under these names."""
    experts_touched: Any    # distinct held experts with at least one pair
    pairs_held: Any         # (token, expert) pairs routed to a held expert
    pairs_routed: Any       # all pairs of the tokens that routed
    expert_load_max: Any    # the busiest held expert's pairs
    expert_load_mean: Any   # pairs_held over the held experts (float32)

    @staticmethod
    def zero():
        z = jnp.zeros((), jnp.int32)
        return RouteStats(z, z, z, z, jnp.zeros((), jnp.float32))

    def __add__(self, other):
        return RouteStats(*(a + b for a, b in zip(self, other)))


def group_limited_topk(scores, bias, *, n_group: int, topk_group: int,
                       top_k: int, routed_scale: float,
                       norm_topk_prob: bool = True):
    """``scores`` f32 ``[T, E]`` (after the sigmoid), ``bias`` ``[E]``.
    Returns ``(idx int32 [T, top_k], weight f32 [T, top_k])``."""
    t, e = scores.shape
    choice = (scores + bias[None]).reshape(t, n_group, e // n_group)
    group_score = jax.lax.top_k(choice, 2)[0].sum(-1)          # [T, G]
    _, keep = jax.lax.top_k(group_score, topk_group)           # [T, Gk]
    kept = (keep[:, :, None] == jnp.arange(n_group)[None, None]).any(1)
    choice = jnp.where(kept[:, :, None], choice, -jnp.inf).reshape(t, e)
    _, idx = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    if norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * routed_scale


def sort_pairs_by_expert(local, held, n_held: int, tile: int):
    """Lay the held pairs out by expert in whole tiles.

    ``local`` int32 ``[P]`` (expert index inside the held range, anything
    where not held), ``held`` bool ``[P]``. The layout has room for every
    pair on one expert: ``P + n_held * (tile - 1)`` rows rounded up to
    tiles. Returns ``(row_pair [M] int32 — the pair each row carries, 0 for
    padding rows; dest [P] int32 — the row of each pair, M where not held;
    tile_expert [M / tile]; n_active [1]; counts [n_held])``."""
    p = local.shape[0]
    m = -(-(p + n_held * (tile - 1)) // tile) * tile
    key = jnp.where(held, local, n_held)
    counts = (key[:, None] == jnp.arange(n_held)[None]).sum(0, jnp.int32)
    order = jnp.argsort(key, stable=True)
    skey = key[order]
    padded = -(-counts // tile) * tile
    start = jnp.cumsum(counts) - counts
    pend = jnp.cumsum(padded)
    pstart = pend - padded
    sk = jnp.minimum(skey, n_held - 1)
    rank = jnp.arange(p, dtype=jnp.int32) - start[sk]
    dest_sorted = jnp.where(skey < n_held, pstart[sk] + rank, m)
    row_pair = jnp.zeros((m,), jnp.int32).at[dest_sorted].set(
        order.astype(jnp.int32), mode="drop")
    dest = jnp.zeros((p,), jnp.int32).at[order].set(
        dest_sorted.astype(jnp.int32))
    n_active = pend[-1] // tile
    first_row = jnp.minimum(jnp.arange(m // tile), n_active - 1) * tile
    tile_expert = jnp.clip(
        jnp.searchsorted(pend, first_row, side="right"), 0, n_held - 1)
    return (row_pair, dest, tile_expert.astype(jnp.int32),
            n_active.astype(jnp.int32)[None], counts)


def held_expert_ffn(x, idx, weight, routes, w_gate, w_up, w_down, *,
                    held_lo: int):
    """The held experts' part of the routed sum.

    ``x`` ``[T, d]``; ``idx``/``weight`` ``[T, k]`` from the router;
    ``routes`` bool ``[T]`` (False: the token takes no part — padding, a
    slot that is not live); the stacked kernels hold experts
    ``held_lo : held_lo + E_held``. Returns ``(y [T, d] in x.dtype,
    RouteStats)``."""
    t, d = x.shape
    k = idx.shape[1]
    n_held = w_gate.shape[0]
    local = (idx - held_lo).reshape(-1)
    held = ((local >= 0) & (local < n_held)
            & jnp.repeat(routes, k, total_repeat_length=t * k))
    tile = NARROW_TILE if t * k <= 4096 else WIDE_TILE
    with jax.named_scope("moe_route"):
        row_pair, dest, tile_expert, n_active, counts = sort_pairs_by_expert(
            local, held, n_held, tile)
    with jax.named_scope("moe_experts"):
        y_rows = grouped_swiglu(x[row_pair // k], tile_expert, n_active,
                                w_gate, w_up, w_down, tile=tile)
        m = y_rows.shape[0]
        y_pairs = y_rows[jnp.minimum(dest, m - 1)].reshape(t, k, d)
        w = jnp.where(held.reshape(t, k), weight, 0.0)
        y = jnp.einsum("tk,tkd->td", w, y_pairs.astype(jnp.float32))
    stats = RouteStats(
        experts_touched=(counts > 0).sum(dtype=jnp.int32),
        pairs_held=counts.sum(dtype=jnp.int32),
        pairs_routed=routes.sum(dtype=jnp.int32) * k,
        expert_load_max=counts.max().astype(jnp.int32),
        expert_load_mean=counts.sum(dtype=jnp.float32) / n_held)
    return y.astype(x.dtype), stats


class HeldExperts(nn.Module):
    """Router over ``n_experts`` + the stacked SwiGLU kernels of the experts
    ``held_lo:held_hi``. ``__call__(x [T, d], routes [T] bool or None)``
    returns ``(held part of the routed sum, RouteStats)``; what every chip
    computes alike (a shared expert) is the caller's to add, once."""
    n_experts: int
    held_lo: int
    held_hi: int
    d_expert: int
    top_k: int
    n_group: int
    topk_group: int
    routed_scale: float
    norm_topk_prob: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, routes: Optional[jax.Array] = None):
        t, d = x.shape
        n_held = self.held_hi - self.held_lo
        if routes is None:
            routes = jnp.ones((t,), bool)
        with jax.named_scope("moe_route"):
            w_r = self.param("router", nn.initializers.lecun_normal(),
                             (d, self.n_experts), self.dtype)
            bias = self.param("router_bias", nn.initializers.zeros,
                              (self.n_experts,), jnp.float32)
            scores = jax.nn.sigmoid(jnp.matmul(
                x.astype(jnp.float32), w_r.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            idx, weight = group_limited_topk(
                scores, bias.astype(jnp.float32), n_group=self.n_group,
                topk_group=self.topk_group, top_k=self.top_k,
                routed_scale=self.routed_scale,
                norm_topk_prob=self.norm_topk_prob)
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=0)
        w_gate = self.param("w_gate", init, (n_held, d, self.d_expert),
                            self.dtype)
        w_up = self.param("w_up", init, (n_held, d, self.d_expert),
                          self.dtype)
        w_down = self.param("w_down", init, (n_held, self.d_expert, d),
                            self.dtype)
        return held_expert_ffn(x, idx, weight, routes, w_gate, w_up, w_down,
                               held_lo=self.held_lo)
