"""The residual path of manifold-constrained hyper-connections
(models/hybrid.py: ``sinkhorn``, ``HyperConnection``, ``HybridBlock`` with
``hc_mult`` > 1) at toy size on the CPU, against the plain reference the
benchmark keeps (benchmark/references/xing_mhc.py); and that ``hc_mult`` 1 is
the model as it was: no parameter of the maps, the same logits."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.references import xing_mhc as ref
from chainermn_tpu.models.hybrid import (HybridBlock, HybridLM,
                                         HyperConnection, layer_pattern,
                                         sinkhorn)

from tests.models_tests.test_hybrid import SIZES as LING_SIZES, jitter

YARN = dict(type="yarn", factor=64, original_max_position_embeddings=4096,
            beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
SIZES = dict(vocab=256, d_model=64, n_heads=4, d_head=16, d_ff=128,
             max_len=96, d_nope=16, d_rope=8, kv_rank=32, rope_theta=1e4,
             n_experts=16, held_lo=0, held_hi=16, d_expert=32, d_shared=32,
             top_k=4, n_group=1, topk_group=1, routed_scale=2.0, hc_mult=4,
             q_rank=24, mla_gate=False, rope_scaling=YARN, mla_block=16)
PATTERN = (("mla", "dense"), ("mla", "moe"), ("mla", "moe"))


def ref_cfg(model, q_block=16):
    return dict(n_heads=model.n_heads, d_head=model.d_head,
                d_nope=model.d_nope, d_rope=model.d_rope,
                kv_rank=model.kv_rank, rope_theta=model.rope_theta,
                rope_scaling=dict(model.rope_scaling), top_k=model.top_k,
                routed_scale=model.routed_scale, norm_eps=model.norm_eps,
                hc_mult=model.hc_mult,
                hc_sinkhorn_iters=model.hc_sinkhorn_iters,
                hc_eps=model.hc_eps, hc_clamp=model.hc_clamp,
                q_block=q_block, pattern=[list(p) for p in model.pattern])


@functools.lru_cache(maxsize=None)
def setup(**over):
    model = HybridLM(pattern=PATTERN, **dict(SIZES, **over))
    params = jitter(model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))["params"])
    return model, params


def reference_logits(model, params, tokens, q_block=16):
    """The reference's full forward on ``tokens [B, L]`` (L a multiple of
    ``q_block``)."""
    layers = [ref.canonical_layer(params[f"block_{i}"])
              for i in range(model.n_layers)]
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(
            jnp.asarray(tokens), layers, ref.canonical_rest(params),
            ref_cfg(model, q_block)))


@pytest.mark.parametrize("extreme", [-30.0, 30.0, "diagonal", "hollow"])
def test_sinkhorn_rows_and_columns_sum_to_one_from_clamped_extremes(extreme):
    """Every entry at one end of the clamp (the logits reach +-40 before
    it), and both ends in one matrix: the diagonal at +30 and the rest at
    -30 (a permutation: the identity), and the other way round."""
    rs = np.random.RandomState(0)
    if isinstance(extreme, str):
        sign = 1.0 if extreme == "diagonal" else -1.0
        logits = sign * np.where(np.eye(4, dtype=bool), 40.0, -40.0) + (
            rs.randn(5, 4, 4))
    else:
        logits = np.full((5, 4, 4), extreme * 4 / 3) + rs.randn(5, 4, 4)
    m = np.asarray(sinkhorn(jnp.clip(jnp.asarray(logits, jnp.float32),
                                     -30.0, 30.0), 20, 1e-6))
    assert np.isfinite(m).all() and (m >= 0).all()
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)
    want = np.asarray(ref.sinkhorn(jnp.clip(jnp.asarray(
        logits, jnp.float32), -30.0, 30.0), 20, 1e-6))
    np.testing.assert_allclose(m, want, rtol=1e-6, atol=1e-7)


def test_maps_are_float32_and_match_the_reference_at_four_streams():
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(2, 5, 4, 64), jnp.float32)
    hc = HyperConnection(4)
    p = jitter(hc.init(jax.random.PRNGKey(0), x)["params"])
    p = dict(p, alpha=jnp.asarray([0.7, -0.4, 0.9]))
    pre, post, res = hc.apply({"params": p}, x)
    assert pre.dtype == post.dtype == res.dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        want = ref.hc_maps(x, p, dict(norm_eps=1e-6, hc_clamp=30.0,
                                      hc_sinkhorn_iters=20, hc_eps=1e-6))
    for got, w in zip((pre, post, res), want):
        np.testing.assert_allclose(got, w, rtol=2e-5, atol=2e-6)
    assert 0 < float(pre.min()) and float(pre.max()) < 1
    assert 0 < float(post.min()) and float(post.max()) < 2
    # 20 rounds end on the columns: they sum to 1; the rows of a matrix
    # this far from uniform are still a per cent off (the model's own
    # approximation, the reference's too)
    np.testing.assert_allclose(np.asarray(res).sum(-2), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res).sum(-1), 1.0, atol=3e-2)


@pytest.mark.parametrize("layer", [0, 1])
def test_block_matches_the_reference_at_four_streams(layer):
    """One layer (dense feed-forward, then the routed one) on a random
    state of 4 streams: the maps, the mixer and the feed-forward between
    them, the spread of the sub-layer's output over the streams."""
    model, params = setup()
    kind = PATTERN[layer]
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(2, 32, 4, 64), jnp.float32)
    blk = HybridBlock(*kind, model.dims())
    got, _ = blk.apply(
        {"params": params[f"block_{layer}"]}, x, jnp.zeros((2,), jnp.int32),
        jnp.full((2,), 32, jnp.int32), jnp.ones((2,), bool))
    with jax.default_matmul_precision("highest"):
        want = ref.block(x, ref.canonical_layer(params[f"block_{layer}"]),
                         kind, ref_cfg(model))
    assert got.shape == (2, 32, 4, 64)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_model_matches_the_reference_and_the_streams_matter():
    model, params = setup()
    tokens = np.random.RandomState(3).randint(0, 256, (2, 48))
    got = np.asarray(model.apply({"params": params}, jnp.asarray(tokens)))
    want = reference_logits(model, params, tokens)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)
    # a map that is ignored would show: move one b_res and the logits move
    moved = jax.tree_util.tree_map(lambda a: a, params)
    moved["block_1"]["hc_ffn"]["b_res"] = (
        params["block_1"]["hc_ffn"]["b_res"] + jnp.eye(4)[::-1] * 3.0)
    other = np.asarray(model.apply({"params": moved}, jnp.asarray(tokens)))
    assert np.abs(other - got).max() > 1e-2


def test_one_stream_creates_no_parameter_of_the_maps_and_is_the_plain_sum():
    """``hc_mult`` 1 (the default, what the Ling configuration runs) builds
    the block as it was: no hyper-connection leaf, the residual the plain
    sum, the same numbers whether the new fields are left out or given
    their defaults."""
    sizes = dict(LING_SIZES)
    pattern = layer_pattern(4, 3, 1)
    plain = HybridLM(pattern=pattern, **sizes)
    spelled = HybridLM(pattern=pattern, hc_mult=1, q_rank=None, mla_gate=True,
                       rope_scaling=None, mla_block=0, **sizes)
    params = plain.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    names = {"/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert not any("hc_" in n or "phi" in n or "qa_proj" in n for n in names)
    assert {"block_2/mla/q_proj/kernel", "block_2/mla/g_proj/kernel",
            "block_0/kda/a_log"} <= names
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        spelled.init(jax.random.PRNGKey(0),
                     jnp.zeros((1, 8), jnp.int32))["params"])
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 40)))
    a = np.asarray(plain.apply({"params": params}, tokens))
    b = np.asarray(spelled.apply({"params": params}, tokens))
    assert np.array_equal(a, b)
    # the residual is the plain sum: with every sub-layer's output
    # projection zeroed the logits are the head on the embedding alone
    zeroed = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.zeros_like(leaf) if any(
            getattr(k, "key", "") in ("o_proj", "down", "w_down")
            for k in path) else leaf, params)
    from chainermn_tpu.models.hybrid import RMSNorm
    emb = params["tok_emb"]["embedding"][tokens]
    y = RMSNorm().apply({"params": params["norm_f"]}, emb)
    want = y @ params["lm_head"]["kernel"]
    np.testing.assert_allclose(
        plain.apply({"params": zeroed}, tokens), want, rtol=1e-5, atol=1e-5)
