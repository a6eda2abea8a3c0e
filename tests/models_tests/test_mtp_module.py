"""The multi-token-prediction module of ``models/hybrid.py`` (``n_mtp`` 1) at
toy size on the CPU, against the benchmark's plain reference
(benchmark/references/deepseek_mtp.py): the main logits and the module's,
teacher-forced over a whole sequence; that ``n_mtp`` 0 creates no parameter
and leaves the other configurations' logits as they were; that the embedding
and the head are shared, one leaf each; and that the 16 shares of an expert
layer add up to the uncut layer."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.references import deepseek_mtp as ref
from benchmark.references import ling_hybrid, xing_mhc
from chainermn_tpu.models.hybrid import HybridLM, MTPModule
from chainermn_tpu.serving.state_cache import (init_state_cache,
                                               recurrent_leaves)

YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
PATTERN = (("mla", "dense"), ("mla", "moe"), ("mla", "moe"))
SIZES = dict(vocab=101, d_model=32, n_heads=4, d_head=8, d_ff=48, max_len=64,
             d_nope=8, d_rope=4, kv_rank=16, q_rank=12, mla_gate=False,
             rope_theta=1e4, rope_scaling=YARN, n_experts=16, held_lo=4,
             held_hi=8, d_expert=16, d_shared=16, top_k=4, n_group=4,
             topk_group=2, routed_scale=2.5, mla_block=16)
REF_CFG = dict(n_heads=4, d_head=8, d_nope=8, d_rope=4, kv_rank=16,
               rope_theta=1e4, rope_scaling=YARN, n_group=4, topk_group=2,
               top_k=4, routed_scale=2.5, held_lo=4, norm_eps=1e-6,
               pattern=PATTERN, q_block=8)


def setup(seed=0, **over):
    model = HybridLM(pattern=PATTERN, n_mtp=1, **{**SIZES, **over})
    params = model.init(jax.random.PRNGKey(seed),
                        np.zeros((1, 8), np.int32))["params"]
    # a router bias that matters, and norms that are not all ones
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for (path, leaf), key in zip(leaves, keys):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "router_bias":
            leaf = 0.05 * jax.random.normal(key, leaf.shape, leaf.dtype)
        elif name == "scale":
            leaf = 1.0 + 0.1 * jax.random.normal(key, leaf.shape, leaf.dtype)
        out.append(leaf)
    return model, jax.tree_util.tree_unflatten(tree, out)


def reference_logits(params, tokens, next_tokens, quant=ref.identity):
    layers = [ref.canonical_layer(params[f"block_{i}"])
              for i in range(len(PATTERN))]
    with jax.default_matmul_precision("highest"):
        return ref.forward(jnp.asarray(tokens), jnp.asarray(next_tokens),
                           layers, ref.canonical_mtp(params["mtp_0"]),
                           ref.canonical_rest(params), REF_CFG, quant)


def teacher_forced(model, params, tokens, next_tokens):
    logits, hidden = model.apply({"params": params}, tokens,
                                 return_hidden=True)
    drafts = model.apply({"params": params}, next_tokens, hidden=hidden)
    return logits, drafts


def test_main_and_module_logits_match_the_reference_teacher_forced():
    model, params = setup()
    rs = np.random.RandomState(3)
    seq = rs.randint(0, 101, (2, 25)).astype(np.int32)
    tokens, nxt = seq[:, :-1], seq[:, 1:]
    got_main, got_mtp = teacher_forced(model, params, tokens, nxt)
    want_main, want_mtp = reference_logits(params, tokens, nxt)
    np.testing.assert_allclose(got_main, want_main, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(got_mtp, want_mtp, atol=2e-4, rtol=2e-4)
    # the two are different predictions: the module's is not the main's
    assert float(jnp.abs(got_mtp - got_main).max()) > 0.05


def test_the_module_reads_the_token_that_follows_and_the_hidden_state():
    """Change ``t_{i+1}`` at one place: the main logits before it stay, the
    module's logits at ``i`` move (it embeds the next token); zero the
    hidden state: the module's logits move everywhere."""
    model, params = setup()
    rs = np.random.RandomState(4)
    seq = rs.randint(0, 101, (1, 17)).astype(np.int32)
    tokens, nxt = seq[:, :-1], seq[:, 1:]
    _, base = teacher_forced(model, params, tokens, nxt)
    other = nxt.copy()
    other[0, 9] = (other[0, 9] + 1) % 101
    logits, hidden = model.apply({"params": params}, tokens,
                                 return_hidden=True)
    moved = model.apply({"params": params}, other, hidden=hidden)
    assert np.allclose(moved[0, :9], base[0, :9], atol=1e-6)
    assert float(jnp.abs(moved[0, 9] - base[0, 9]).max()) > 1e-3
    blind = model.apply({"params": params}, nxt,
                        hidden=jnp.zeros_like(hidden))
    assert float(jnp.abs(blind - base).max(-1).min()) > 1e-3


def test_embedding_and_head_are_shared_one_leaf_each():
    model, params = setup()
    flat = ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    assert sum(p.endswith("embedding") for p in flat) == 1
    assert sum(p.startswith("lm_head") for p in flat) == 1
    assert set(params["mtp_0"]) == {"norm_h", "norm_e", "eh_proj", "block",
                                    "norm_out"}
    assert params["mtp_0"]["eh_proj"]["kernel"].shape == (64, 32)
    assert set(params["mtp_0"]["block"]) == {"norm_mix", "mla", "norm_ffn",
                                             "moe", "shared"}
    # the head's kernel moves both predictions
    rs = np.random.RandomState(5)
    seq = rs.randint(0, 101, (1, 9)).astype(np.int32)
    a_main, a_mtp = teacher_forced(model, params, seq[:, :-1], seq[:, 1:])
    scaled = dict(params, lm_head={"kernel": 2.0 * params["lm_head"]["kernel"]})
    b_main, b_mtp = teacher_forced(model, scaled, seq[:, :-1], seq[:, 1:])
    np.testing.assert_allclose(b_main, 2.0 * a_main, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(b_mtp, 2.0 * a_mtp, rtol=1e-5, atol=1e-6)


def test_the_module_has_its_own_page_and_the_slot_a_draft():
    model, _ = setup()
    cache = init_state_cache(model, 3, 32)
    assert cache["mtp_0"]["block"]["mla"]["ckv"].shape == (3, 32, 128)
    assert cache["draft"].shape == (3,) and cache["draft"].dtype == jnp.int32
    assert recurrent_leaves(model) == []
    plain = init_state_cache(model.clone(n_mtp=0), 3, 32)
    assert "mtp_0" not in plain and "draft" not in plain


def test_more_than_one_module_is_refused():
    model = HybridLM(pattern=PATTERN, n_mtp=2, **SIZES)
    with pytest.raises(ValueError, match="deeper drafting is not built"):
        model.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    with pytest.raises(ValueError, match="n_mtp 1"):
        plain = HybridLM(pattern=PATTERN, **SIZES)
        p = plain.init(jax.random.PRNGKey(0),
                       np.zeros((1, 8), np.int32))["params"]
        plain.apply({"params": p}, np.zeros((1, 8), np.int32),
                    hidden=jnp.zeros((1, 8, 32)))


# -- n_mtp 0 leaves the other configurations alone ---------------------------

def ling_toy():
    from tests.serving_tests.test_state_cache import setup as ling_setup
    return ling_setup()


def xing_toy():
    from tests.models_tests.test_hyper_connections import setup as xing_setup
    return xing_setup()


@pytest.mark.parametrize("toy", [ling_toy, xing_toy], ids=["ling", "xing"])
def test_n_mtp_0_creates_no_parameter_and_keeps_the_logits_bitwise(toy):
    """The toy models of the two hybrid configurations the benchmark runs:
    their parameter trees have no module, and their logits are bitwise what
    the same tree gives a model that states ``n_mtp=0`` outright and what
    ``return_hidden`` hands back beside the hidden state."""
    model, params = toy()[:2]
    assert model.n_mtp == 0 and "mtp_0" not in params
    fresh = model.init(jax.random.PRNGKey(0),
                       np.zeros((1, 8), np.int32))["params"]
    assert "mtp_0" not in fresh
    rs = np.random.RandomState(6)
    tokens = rs.randint(0, model.vocab, (2, 24)).astype(np.int32)
    want = model.apply({"params": params}, tokens)
    got, hidden = model.clone(n_mtp=0).apply({"params": params}, tokens,
                                             return_hidden=True)
    assert np.array_equal(np.asarray(want), np.asarray(got))
    assert hidden.shape == (2, 24, model.d_model)


@pytest.mark.parametrize("which", ["ling", "xing"])
def test_the_decode_programs_of_the_other_configurations_lower_alike(which):
    """``n_mtp`` 0 against a model that never heard of the field: the
    one-token decode program lowers to the same text."""
    model, params = (ling_toy if which == "ling" else xing_toy)()[:2]
    from chainermn_tpu.serving.state_cache import state_decode_apply

    def lowered(m):
        dm = m.clone(decode=True, max_len=32)
        cache = init_state_cache(m, 2, 32)
        return jax.jit(lambda p, c, t: state_decode_apply(dm, p, c, t)).lower(
            params, cache, jnp.zeros((2,), jnp.int32)).as_text()

    assert lowered(model) == lowered(model.clone(n_mtp=0))
    assert "mtp" not in lowered(model)


# -- the share is tied to the model ------------------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Guide §4: at toy size, the routed parts that the shares of one expert
    layer give — 16 experts held 1 at a time, and 4 at a time —, with the
    shared expert counted once, add up to what the uncut reference (all 16
    experts held) gives for the whole layer. Program and reference alike."""
    rs = np.random.RandomState(7)
    d, e, f = 32, 16, 16
    y = jnp.asarray(rs.randn(40, d), jnp.float32)
    p = {"router": jnp.asarray(rs.randn(d, e) * d ** -0.5, jnp.float32),
         "router_bias": jnp.asarray(0.05 * rs.randn(e), jnp.float32),
         "w_gate": jnp.asarray(rs.randn(e, d, f) * d ** -0.5, jnp.float32),
         "w_up": jnp.asarray(rs.randn(e, d, f) * d ** -0.5, jnp.float32),
         "w_down": jnp.asarray(rs.randn(e, f, d) * f ** -0.5, jnp.float32)}
    shared = [jnp.asarray(rs.randn(d, f) * d ** -0.5, jnp.float32),
              jnp.asarray(rs.randn(d, f) * d ** -0.5, jnp.float32),
              jnp.asarray(rs.randn(f, d) * f ** -0.5, jnp.float32)]
    mm = jnp.matmul
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_ffn(y, p, dict(REF_CFG, held_lo=0), mm) \
            + ref.swiglu(y, *shared, mm)

        def share(lo, hi, routed):
            cut = dict(p, **{k: p[k][lo:hi]
                             for k in ("w_gate", "w_up", "w_down")})
            return routed(cut, lo, hi)

        by_ref = lambda cut, lo, hi: ref.routed_ffn(
            y, cut, dict(REF_CFG, held_lo=lo), mm)

        from chainermn_tpu.parallel.expert_share import HeldExperts

        def by_program(cut, lo, hi):
            layer = HeldExperts(e, lo, hi, f, 4, 4, 2, 2.5)
            return layer.apply({"params": {
                k: cut[k] for k in ("router", "router_bias", "w_gate",
                                    "w_up", "w_down")}}, y)[0]

        for routed in (by_ref, by_program):
            for width in (1, 4):
                parts = sum(share(lo, lo + width, routed)
                            for lo in range(0, e, width))
                np.testing.assert_allclose(
                    parts + ref.swiglu(y, *shared, mm), whole, atol=2e-5,
                    rtol=2e-5)
    # and a share is not the whole: what the absent experts add is left out
    assert float(jnp.abs(share(0, 4, by_ref)
                         + ref.swiglu(y, *shared, mm) - whole).max()) > 1e-2


def test_the_reference_imports_nothing_of_the_program():
    import inspect
    src = inspect.getsource(ref)
    assert "chainermn_tpu" not in src.replace(
        "It imports nothing of\nthe program", "")
    assert "import" in src and "from chainermn" not in src
    # the routing rule is the share's: the sibling references agree on it
    rs = np.random.RandomState(8)
    y = jnp.asarray(rs.randn(12, 32), jnp.float32)
    p = {"router": jnp.asarray(rs.randn(32, 16), jnp.float32),
         "router_bias": jnp.asarray(0.05 * rs.randn(16), jnp.float32)}
    a, wa = ref.route(y, p, REF_CFG)
    b, wb, _ = ling_hybrid.route(y, p, REF_CFG)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(wa, wb, rtol=1e-6)
    assert xing_mhc.yarn_mscale(40, 1) == ref.yarn_mscale(40, 1)
    assert isinstance(MTPModule, type)
