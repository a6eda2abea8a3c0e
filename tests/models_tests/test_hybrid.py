"""models/hybrid.py and parallel/expert_share.py at toy size on the CPU,
against the plain reference the benchmark keeps (benchmark/references/
ling_hybrid.py): the two forms of the KDA recurrence against the sequential
scan, prefill then decode through the declared cache against the full
forward, a right-padded row against an unpadded one, MLA's absorbed decode
against its expanded prefill, group-limited routing, the dropless grouped
product under a skewed batch, and the share test."""
import contextlib
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.references import ling_hybrid as ref
from chainermn_tpu.models.hybrid import (HybridLM, kda_chunk, kda_step,
                                         layer_pattern)
from chainermn_tpu.ops import kda_state
from chainermn_tpu.ops.latent_attention import record_paths
from chainermn_tpu.parallel.expert_share import (HeldExperts,
                                                 group_limited_topk,
                                                 held_expert_ffn)
from chainermn_tpu.serving.state_cache import init_state_cache


SIZES = dict(vocab=256, d_model=64, n_heads=4, d_head=16, d_ff=128,
             max_len=160, d_nope=16, d_rope=8, kv_rank=32, rope_theta=1e4,
             n_experts=32, held_lo=8, held_hi=16, d_expert=32, d_shared=32,
             top_k=4, n_group=4, topk_group=2, routed_scale=2.5)
PATTERN = layer_pattern(4, 3, 1)       # kda/dense, kda/moe, mla/moe, kda/moe


def ref_cfg(model):
    return dict(n_heads=model.n_heads, d_head=model.d_head,
                d_nope=model.d_nope, d_rope=model.d_rope,
                kv_rank=model.kv_rank, rope_theta=model.rope_theta,
                kda_lower_bound=model.kda_lower_bound, n_group=model.n_group,
                topk_group=model.topk_group, top_k=model.top_k,
                routed_scale=model.routed_scale, held_lo=model.held_lo,
                norm_eps=model.norm_eps,
                pattern=[list(p) for p in model.pattern])


def jitter(params, seed=1):
    """Move the leaves that init leaves at 0 or 1 (biases, decay
    parameters, norm scales), so a dropped one would show."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        a + 0.3 * jax.random.normal(k, a.shape, a.dtype) if a.ndim == 1
        else a for a, k in zip(leaves, keys)])


@functools.lru_cache(maxsize=None)
def setup(**over):
    model = HybridLM(pattern=PATTERN, **dict(SIZES, **over))
    params = jitter(model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))["params"])
    return model, params


def reference_logits(model, params, tokens):
    layers = [ref.canonical_layer(params[f"block_{i}"])
              for i in range(model.n_layers)]
    return ref.forward(tokens, layers, ref.canonical_rest(params),
                       ref_cfg(model))


def kda_inputs(seed, b, l, h, dk, decay):
    rs = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q, k, v = unit(f(b, l, h, dk)) * dk ** -0.5, unit(f(b, l, h, dk)), \
        f(b, l, h, dk)
    g = -decay * jax.nn.sigmoid(2 * f(b, l, h, dk))
    return q, k, v, g, jax.nn.sigmoid(f(b, l, h)), f(b, h, dk, dk)


@pytest.mark.parametrize("l,decay", [(64, 0.1), (128, 5.0), (192, 1.0)])
def test_kda_chunk_form_matches_the_sequential_scan(l, decay):
    """``decay`` 5 is the gate's floor: 64 tokens of it underflow any
    product of cumulative decays that is not kept relative."""
    q, k, v, g, beta, s0 = kda_inputs(l, 2, l, 3, 16, decay)
    want_o, want_s = ref.kda_scan(q, k, v, g, beta, s0)
    o, s = jax.jit(kda_chunk)(q, k, v, g, beta, s0)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)


def test_kda_one_step_form_matches_the_sequential_scan():
    q, k, v, g, beta, s = kda_inputs(3, 2, 40, 3, 16, 2.0)
    want_o, want_s = ref.kda_scan(q, k, v, g, beta, s)
    outs = []
    for t in range(40):
        o, s = kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s)
        outs.append(jax.block_until_ready(o))
    np.testing.assert_allclose(jnp.stack(outs, 1), want_o, atol=1e-6)
    np.testing.assert_allclose(s, want_s, atol=1e-6)


def test_kda_padding_with_beta_0_and_alpha_1_leaves_the_state_exactly():
    q, k, v, g, beta, s0 = kda_inputs(5, 1, 64, 2, 16, 1.0)
    real = jnp.arange(64) < 23
    g = jnp.where(real[None, :, None, None], g, 0.0)
    beta = jnp.where(real[None, :, None], beta, 0.0)
    _, s = kda_chunk(q, k, v, g, beta, s0)
    _, want = ref.kda_scan(q[:, :23], k[:, :23], v[:, :23], g[:, :23],
                           beta[:, :23], s0)
    np.testing.assert_allclose(s, want, atol=2e-6)


def test_full_forward_matches_the_reference():
    model, params = setup()
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 96)))
    got = model.apply({"params": params}, toks)
    want = reference_logits(model, params, toks)
    np.testing.assert_allclose(got, want, atol=5e-4)


def run_cached(model, params, toks, lens, bucket, total):
    """Prefill rows of true lengths ``lens`` right-padded to ``bucket``,
    then decode one token at a time up to ``total``; returns the logits at
    every position from each row's last prompt token on, and the cache."""
    dm = model.clone(decode=True)
    b = toks.shape[0]
    cache0 = init_state_cache(model, b, model.max_len)
    lens = jnp.asarray(lens, jnp.int32)
    pad = jnp.where(jnp.arange(bucket)[None] < lens[:, None],
                    toks[:, :bucket], 0)
    live = jnp.ones((b,), bool)
    lg, upd = dm.apply({"params": params, "cache": cache0}, pad,
                       lengths=lens, live=live, mutable=["cache", "stats"])
    cache = upd["cache"]
    rows = [[lg[i, int(lens[i]) - 1]] for i in range(b)]
    step = jax.jit(lambda c, t: dm.apply(
        {"params": params, "cache": c}, t, lengths=jnp.ones((b,), jnp.int32),
        live=live, mutable=["cache", "stats"]))
    for j in range(total - int(max(lens))):
        tk = jnp.stack([toks[i, int(lens[i]) + j] for i in range(b)])[:, None]
        lg, upd = step(cache, tk)
        cache = upd["cache"]
        for i in range(b):
            rows[i].append(lg[i, 0])
    return [jnp.stack(r) for r in rows], cache


#: the decode step's recurrence in its two forms (``kda_decode_step``): the
#: toy widths keep ``kda_step``; heads of 128 x 128 are whole lane tiles
STATE_STEP = {"xla": {}, "kernel": dict(d_head=128)}


@contextlib.contextmanager
def state_step(form):
    """Inside, ``kda_decode_step`` takes ``form``: for the kernel its rule
    (``step_kernel_refusal``) is asked as it is on the chip, and the kernel
    then runs where the model's other kernels run off the chip, in the
    Pallas interpreter that is plain HLO. (NOT ``force_tpu_interpret_mode``:
    that one turns every load and store of every kernel of the model into a
    host callback that calls ``jax.numpy``, and beside the eager calls of
    ``run_cached`` the two threads deadlock, PR 40.) On leaving, every call
    traced inside must have noted that form."""
    rule = kda_state.step_kernel_refusal

    def on_the_chip(q, v, state):
        with pytest.MonkeyPatch.context() as chip:
            chip.setattr(kda_state, "on_tpu", lambda: True)
            return rule(q, v, state)

    with pytest.MonkeyPatch.context() as mp, \
            record_paths(kda_state.PATHS) as paths:
        if form == "kernel":
            mp.setattr(kda_state, "step_kernel_refusal", on_the_chip)
        yield
    assert paths and {p.split(":")[0] for p in paths} == {form}, paths


@pytest.mark.parametrize("form", list(STATE_STEP))
def test_prefill_then_decode_through_the_cache_matches_the_full_forward(form):
    model, params = setup(**STATE_STEP[form])
    toks = jnp.asarray(np.random.RandomState(1).randint(0, 256, (2, 100)))
    want = reference_logits(model, params, toks)
    lens = [50, 37]
    with state_step(form):
        rows, cache = run_cached(model, params, toks, lens, 64, 100)
    for i, n in enumerate(lens):
        np.testing.assert_allclose(
            rows[i], want[i, n - 1:n - 1 + rows[i].shape[0]], atol=5e-4)
    assert cache["idx"].tolist() == [100, 87]


def test_a_right_padded_prefill_installs_the_state_of_an_unpadded_one():
    """Row 1 of a [2, 64] bucket holds 37 real tokens; the same 37 tokens
    alone in a [1, 37] call must leave the same recurrent state,
    convolution tail, latent rows and cursor."""
    model, params = setup()
    toks = jnp.asarray(np.random.RandomState(2).randint(0, 256, (2, 64)))
    _, padded = run_cached(model, params, toks, [50, 37], 64, 50)
    _, alone = run_cached(model, params, toks[1:], [37], 37, 37)
    for name in ("block_0", "block_1", "block_3"):
        for leaf in ("state", "conv"):
            np.testing.assert_allclose(
                padded[name]["kda"][leaf][1], alone[name]["kda"][leaf][0],
                atol=2e-5, err_msg=f"{name} {leaf}")
    np.testing.assert_allclose(padded["block_2"]["mla"]["ckv"][1, :37],
                               alone["block_2"]["mla"]["ckv"][0, :37],
                               atol=2e-5)
    assert int(padded["idx"][1]) == int(alone["idx"][0]) == 37


@pytest.mark.parametrize("form", list(STATE_STEP))
def test_a_row_that_is_not_live_keeps_its_state_and_cursor(form):
    model, params = setup(**STATE_STEP[form])
    dm = model.clone(decode=True)
    toks = jnp.asarray(np.random.RandomState(3).randint(0, 256, (2, 40)))
    with state_step(form):
        _, cache = run_cached(model, params, toks, [32, 32], 32, 36)
        _, upd = dm.apply({"params": params, "cache": cache}, toks[:, 36:37],
                          lengths=jnp.ones((2,), jnp.int32),
                          live=jnp.asarray([True, False]),
                          mutable=["cache", "stats"])
    new = upd["cache"]
    assert new["idx"].tolist() == [37, 36]
    for name in ("block_0", "block_1", "block_3"):
        for leaf in ("state", "conv"):
            a, b = cache[name]["kda"][leaf], new[name]["kda"][leaf]
            assert np.array_equal(a[1], b[1]), (name, leaf)
            assert not np.array_equal(a[0], b[0]), (name, leaf)


def test_mla_absorbed_decode_matches_expanded_prefill_and_the_reference():
    """One MLA layer alone: the logits of token t from a one-token call
    against the page (absorbed form) equal the slab's (expanded form) and
    the reference's."""
    model = HybridLM(pattern=(("mla", "dense"),), **SIZES)
    params = jitter(model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))["params"])
    toks = jnp.asarray(np.random.RandomState(4).randint(0, 256, (2, 48)))
    slab = model.apply({"params": params}, toks)
    want = reference_logits(model, params, toks)
    np.testing.assert_allclose(slab, want, atol=2e-4)
    rows, _ = run_cached(model, params, toks, [20, 20], 32, 48)
    np.testing.assert_allclose(rows[0], slab[0, 19:], atol=2e-4)
    np.testing.assert_allclose(rows[1], want[1, 19:], atol=2e-4)


def routing_case(seed, t=64, e=32):
    rs = np.random.RandomState(seed)
    scores = jax.nn.sigmoid(jnp.asarray(rs.randn(t, e), jnp.float32))
    bias = jnp.asarray(0.1 * rs.randn(e), jnp.float32)
    return scores, bias


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_limited_routing_matches_the_reference(seed):
    scores, bias = routing_case(seed)
    idx, w = group_limited_topk(scores, bias, n_group=4, topk_group=2,
                                top_k=4, routed_scale=2.5)
    logit = jnp.log(scores) - jnp.log1p(-scores)
    p = {"router": jnp.eye(32, dtype=jnp.float32), "router_bias": bias}
    chosen, want_w, _ = ref.route(logit, p, dict(
        n_group=4, topk_group=2, top_k=4, routed_scale=2.5))
    assert np.array_equal(np.sort(idx, -1), np.sort(chosen, -1))
    order = np.argsort(np.asarray(idx), -1)
    want_order = np.argsort(np.asarray(chosen), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, -1),
        np.take_along_axis(np.asarray(want_w), want_order, -1), rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)
    groups = np.asarray(idx) // 8
    assert all(len(set(row)) <= 2 for row in groups)


def expert_kernels(seed, e=8, d=64, f=32):
    rs = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rs.randn(*s) / np.sqrt(s[1]), jnp.float32)
    return mk(e, d, f), mk(e, d, f), mk(e, f, d)


def dense_experts(x, idx, w, kernels, lo):
    wg, wu, wd = kernels
    out = jnp.zeros_like(x)
    for e in range(wg.shape[0]):
        share = jnp.sum(jnp.where(idx == lo + e, w, 0.0), -1)
        out = out + share[:, None] * (
            (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return out


TRACES = []


@jax.jit
def traced_ffn(x, idx, w, routes, wg, wu, wd):
    TRACES.append(1)
    return held_expert_ffn(x, idx, w, routes, wg, wu, wd, held_lo=8)


@pytest.mark.parametrize("case", ["spread", "one_expert", "none_held",
                                  "half_routes"])
def test_the_grouped_product_keeps_every_pair_in_one_trace(case):
    """Whatever the routing — every pair on ONE held expert, none on any —
    the held part equals the dense loop over the held experts, nothing is
    dropped, and the program is the one traced for the first case."""
    rs = np.random.RandomState(5)
    t, k = 48, 4
    x = jnp.asarray(rs.randn(t, 64), jnp.float32)
    idx = jnp.asarray(np.stack([rs.choice(32, k, replace=False)
                                for _ in range(t)]), jnp.int32)
    routes = jnp.ones((t,), bool)
    if case == "one_expert":
        idx = jnp.full((t, k), 11, jnp.int32)
    elif case == "none_held":
        idx = idx % 8
    elif case == "half_routes":
        routes = jnp.arange(t) % 2 == 0
    w = jnp.asarray(rs.rand(t, k), jnp.float32)
    kernels = expert_kernels(6)
    y, stats = traced_ffn(x, idx, w, routes, *kernels)
    want = dense_experts(x, idx, jnp.where(routes[:, None], w, 0.0),
                         kernels, 8)
    np.testing.assert_allclose(y, want, atol=2e-5)
    held = np.asarray((idx >= 8) & (idx < 16) & routes[:, None])
    assert int(stats.pairs_held) == held.sum()
    assert int(stats.pairs_routed) == int(routes.sum()) * k
    counts = np.bincount(np.asarray(idx)[held] - 8, minlength=8)
    assert int(stats.experts_touched) == (counts > 0).sum()
    assert int(stats.expert_load_max) == counts.max()
    if case == "one_expert":
        assert int(stats.pairs_held) == t * k and counts.max() == t * k
    assert len(TRACES) == 1


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """The share test: one expert layer, its 32 experts over 4 chips of 8.
    Each chip's held part, summed, plus the shared expert counted ONCE,
    equals the uncut reference's layer output."""
    model, params = setup()
    blk = params["block_1"]
    p = ref.canonical_layer(blk)
    rs = np.random.RandomState(7)
    y = jnp.asarray(rs.randn(40, 64), jnp.float32)
    cfg = dict(ref_cfg(model), held_lo=0)
    # the uncut reference: all 32 experts' kernels, drawn here
    full = expert_kernels(8, e=32)
    uncut = dict(p, w_gate=full[0], w_up=full[1], w_down=full[2])
    mm = jnp.matmul
    want = ref.routed_ffn(y, uncut, cfg, mm) + ref.swiglu(
        y, p["shared_gate"], p["shared_up"], p["shared_down"], mm)
    total = ref.swiglu(y, p["shared_gate"], p["shared_up"],
                       p["shared_down"], mm)
    for chip in range(4):
        lo, hi = 8 * chip, 8 * chip + 8
        layer = HeldExperts(32, lo, hi, 32, 4, 4, 2, 2.5)
        moe = dict(blk["moe"], w_gate=full[0][lo:hi], w_up=full[1][lo:hi],
                   w_down=full[2][lo:hi])
        part, stats = layer.apply({"params": moe}, y)
        total = total + part
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert float(jnp.abs(want).max()) > 0.1
