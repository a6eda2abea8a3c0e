"""Fused LM-head + softmax CE (ops/fused_ce.py): loss, accuracy, and
BOTH gradients must match the unfused logits-materializing computation."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from chainermn_tpu.ops.fused_ce import fused_ce_head, fused_lm_loss

N, D, V = 96, 32, 256          # N not a block multiple: padding path
BR, BV = 64, 128


def _data(seed=0, n=N):
    rs = np.random.RandomState(seed)
    h = jnp.asarray(rs.randn(n, D) * 0.5, jnp.float32)
    w = jnp.asarray(rs.randn(D, V) * 0.2, jnp.float32)
    y = jnp.asarray(rs.randint(0, V, size=(n,)), jnp.int32)
    return h, w, y


def _ref(h, w, y):
    logits = (h @ w).astype(jnp.float32)
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits, y).mean()
    acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
    return loss, acc


@pytest.mark.parametrize("n", [N, BR * 2])   # padded and exact
def test_forward_matches_unfused(n):
    h, w, y = _data(n=n)
    loss, acc = jax.jit(
        lambda h, w, y: fused_ce_head(h, w, y, BR, BV))(h, w, y)
    ref_loss, ref_acc = _ref(h, w, y)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(float(acc), float(ref_acc), rtol=1e-6)


def test_gradients_match_unfused():
    h, w, y = _data(seed=1)

    def fused(h, w):
        return fused_ce_head(h, w, y, BR, BV)[0]

    def ref(h, w):
        return _ref(h, w, y)[0]

    gf = jax.jit(jax.grad(fused, argnums=(0, 1)))(h, w)
    gr = jax.grad(ref, argnums=(0, 1))(h, w)
    for a, b, name in zip(gf, gr, ("dh", "dw")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6, err_msg=name)


def test_bf16_hidden():
    h, w, y = _data(seed=2)
    loss, _ = jax.jit(lambda h, w, y: fused_ce_head(
        h.astype(jnp.bfloat16), w.astype(jnp.bfloat16), y, BR, BV))(
            h, w, y)
    ref_loss, _ = _ref(h, w, y)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-2)


def test_nondivisible_vocab_raises():
    h, w, y = _data()
    with pytest.raises(ValueError, match="multiple"):
        fused_ce_head(h, w, y, BR, 100)


def test_fused_lm_loss_rejects_mutable():
    """A model with mutable state must not silently drop its updates
    (the guard mirrors the MoE 'losses' refusal)."""
    from chainermn_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab=BV, d_model=D, n_heads=2, n_layers=1,
                          d_ff=32, max_len=8)
    x = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="mutable"):
        fused_lm_loss(model, {}, x, x, mutable=("batch_stats",))


def test_fused_lm_loss_end_to_end():
    """Step-factory path: same loss/acc/grads as lm_loss_with_aux on a
    real TransformerLM, and a few SGD steps actually learn."""
    from chainermn_tpu.models.transformer import (
        TransformerLM, lm_loss_with_aux)

    model = TransformerLM(vocab=BV * 2, d_model=D, n_heads=2, n_layers=2,
                          d_ff=64, max_len=32, pos_emb="rope",
                          attention="reference")
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randint(0, BV * 2, size=(4, 32)), jnp.int32)
    y = jnp.asarray(rs.randint(0, BV * 2, size=(4, 32)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x)["params"]

    def f_loss(p):
        return fused_lm_loss(model, p, x, y,
                             block_rows=BR, block_v=BV)[0]

    def r_loss(p):
        return lm_loss_with_aux(model, p, x, y)[0]

    lf, gf = jax.jit(jax.value_and_grad(f_loss))(params)
    lr, gr = jax.jit(jax.value_and_grad(r_loss))(params)
    np.testing.assert_allclose(float(lf), float(lr), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-4, atol=2e-6),
        gf, gr)

    p = params
    losses = []
    step = jax.jit(jax.value_and_grad(f_loss))
    for _ in range(15):
        l, g = step(p)
        losses.append(float(l))
        p = jax.tree_util.tree_map(lambda a, b: a - 0.5 * b, p, g)
    assert losses[-1] < 0.8 * losses[0], losses


# full-suite only: the quick battery must stay well under its 120 s
# budget and these interpret-mode kernel tests cost ~25 s


def test_dw_tile_fallback_non_dividing_halved_tile():
    """Regression (r5 review): with block_v > 1024 and vocab not a
    multiple of 1024, the dW pass's halved tile would not divide the
    vocab — the old code left the tail dW columns UNWRITTEN (silently
    zero gradients for part of the head). The fallback must keep every
    column correct; compare against the unfused XLA loss's gradients."""
    import optax

    rng = np.random.RandomState(31)
    n, d, v = 128, 32, 1536  # v % 1024 != 0, block_v = v > 1024
    h = jnp.asarray(rng.randn(n, d).astype(np.float32) * 0.3)
    w = jnp.asarray(rng.randn(d, v).astype(np.float32) * 0.05)
    y = jnp.asarray(rng.randint(0, v, size=(n,)).astype(np.int32))

    def loss_fused(h, w):
        return fused_ce_head(h, w, y, 128, v)[0]

    def loss_ref(h, w):
        return optax.softmax_cross_entropy_with_integer_labels(
            (h @ w).astype(jnp.float32), y).mean()

    gf = jax.grad(loss_fused, argnums=(0, 1))(h, w)
    gr = jax.grad(loss_ref, argnums=(0, 1))(h, w)
    # the tail columns (>= 1024) are exactly where the old bug zeroed dW
    tail = np.asarray(gf[1][:, 1024:])
    assert np.abs(tail).max() > 0, "tail dW columns are all zero"
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


def test_fused_lm_loss_through_data_parallel_step(comm):
    """The path the gated LM rows and chip_smoke.py take: fused_lm_loss
    differentiated INSIDE make_data_parallel_train_step's shard_map
    (check_vma on). The head kernel is replicated and the hidden states
    vary over the mesh axis, so dW must come back reduced to the primal's
    replicated type — the custom_vjp contract jax checks at trace time —
    and allreduce_grad must then only scale it. One SGD(lr=1) step turns
    the parameter delta into the gradient; loss and every gradient leaf
    must match the unfused lm_loss_with_aux step."""
    import functools

    import chainermn_tpu
    from chainermn_tpu.models.transformer import (
        TransformerLM, lm_loss_with_aux)
    from chainermn_tpu.training.step import make_data_parallel_train_step

    model = TransformerLM(vocab=BV * 2, d_model=D, n_heads=2, n_layers=1,
                          d_ff=64, max_len=16, pos_emb="rope",
                          attention="reference")
    rs = np.random.RandomState(4)
    toks = rs.randint(0, BV * 2, size=(2 * comm.size, 17)).astype(np.int32)
    x, y = toks[:, :-1], toks[:, 1:]
    params = comm.bcast_data(
        model.init(jax.random.PRNGKey(0), x[:1])["params"])
    opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(1.0), comm)

    def one_step(loss_fn):
        step = make_data_parallel_train_step(model, opt, comm,
                                             loss_fn=loss_fn, donate=False)
        (new, _), m = step((params, opt.init(params)), x, y)
        grads = jax.tree_util.tree_map(lambda a, b: a - b, params, new)
        return float(m["main/loss"]), float(m["main/accuracy"]), grads

    lf, af, gf = one_step(functools.partial(fused_lm_loss, block_rows=BR,
                                            block_v=BV))
    lr, ar, gr = one_step(lm_loss_with_aux)
    np.testing.assert_allclose(lf, lr, rtol=1e-5)
    np.testing.assert_allclose(af, ar, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-4, atol=2e-6),
        gf, gr)
