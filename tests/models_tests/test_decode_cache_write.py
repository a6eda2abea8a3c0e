"""The decode step's cache write through the model (ops/page_write.py's
kernel in ``TransformerBlock``'s per-slot branch) against the write the tree
had before it, ``vmap(dynamic_update_slice)``, kept here as the oracle: the
same ``decode_k_apply`` leaves the same cache, byte for byte, and the same
tokens. The widths give a cache row of whole tiles (2 kv heads of 128), so
the kernel is what runs, interpreted; the serving tests' toy widths keep the
``vmap`` form (tests/ops_tests/test_page_write.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import TransformerLM
from chainermn_tpu.models import transformer
from chainermn_tpu.ops import page_write as pw
from chainermn_tpu.serving.kv_cache import (ServingStep, decode_k_apply,
                                            init_cache)

VOCAB, SLOTS, CAP, LAYERS, K = 43, 5, 12, 2, 4


def _model(attention, dtype=jnp.float32):
    return TransformerLM(vocab=VOCAB, d_model=256, n_heads=2, d_ff=64,
                         n_layers=LAYERS, max_len=64, attention=attention,
                         pos_emb="rope", dtype=dtype)


def _params(model):
    return model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 4), jnp.int32))["params"]


def _counted(monkeypatch):
    calls = []
    real = pw.page_write_rows
    monkeypatch.setattr(
        pw, "page_write_rows",
        lambda *a, **k: calls.append(a[0].shape) or real(*a, **k))
    return calls


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("attention", ["flash", "reference"])
def test_decode_k_leaves_the_cache_and_tokens_of_the_old_write(
        monkeypatch, attention, dtype):
    """Live slots (one about to wrap its ring, one retiring on its budget
    mid-scan), a free slot and a slot parked mid-prefill: every row of the
    grid is written at its pinned cursor in each of the 4 steps."""
    dtype = jnp.dtype(dtype)
    model = _model(attention, dtype)
    params = _params(model)
    rng = np.random.RandomState(3)
    cache = jax.tree_util.tree_map(
        lambda x: (jnp.asarray([3, CAP - 2, 0, 7, 30], jnp.int32)
                   if x.dtype == jnp.int32
                   else jnp.asarray(rng.randn(*x.shape), dtype)),
        init_cache(model, SLOTS, CAP, dtype))
    args = (jnp.asarray([1, 2, 3, 4, 5], jnp.int32),            # tokens
            jax.random.split(jax.random.PRNGKey(5), SLOTS),     # keys
            jnp.asarray([0.0, 0.8, 0.0, 0.0, 0.0], jnp.float32),
            jnp.asarray([0, 5, 0, 0, 0], jnp.int32),
            jnp.full((SLOTS,), -1, jnp.int32),                  # no eos
            jnp.asarray([9, 9, 0, 0, 2], jnp.int32),            # remaining
            jnp.asarray([True, True, False, False, True]),      # live
            jnp.asarray([0, 0, 0, 5, 0], jnp.int32))            # park

    def run():
        return jax.jit(lambda p, c: decode_k_apply(
            model, p, c, *args, K))(params, cache)

    calls = _counted(monkeypatch)
    toks, last, keys, new = run()
    assert len(calls) == LAYERS          # traced once inside the scan
    monkeypatch.setattr(transformer, "write_rows", pw.vmap_write_rows)
    toks0, last0, keys0, old = run()
    assert len(calls) == LAYERS          # the oracle never reached the kernel
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(toks0))
    np.testing.assert_array_equal(np.asarray(keys), np.asarray(keys0))
    np.testing.assert_array_equal(np.asarray(last), np.asarray(last0))
    for name in old:
        for leaf in ("k", "v", "idx"):
            a, b = np.asarray(new[name][leaf]), np.asarray(old[name][leaf])
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))
    # and it did write: a live slot's rows at its cursor changed
    assert not np.array_equal(np.asarray(new["block_0"]["k"][0, 3:7]),
                              np.asarray(cache["block_0"]["k"][0, 3:7]))


@pytest.mark.parametrize("devices,kernel", [(None, True), (1, True),
                                            (2, False)],
                         ids=["no-mesh", "one-device-mesh",
                              "two-device-mesh"])
def test_a_several_device_mesh_keeps_the_partitionable_write(
        monkeypatch, devices, kernel):
    """``ServingStep`` picks at trace time from the mesh it holds: pages
    split over several devices keep ``vmap(dynamic_update_slice)``, one
    device (mesh or not) takes the kernel; the logits are the same."""
    from jax.sharding import Mesh

    model = _model("reference")
    params = _params(model)
    mesh = (None if devices is None
            else Mesh(np.array(jax.devices()[:devices]), ("serve",)))
    calls = _counted(monkeypatch)
    step = ServingStep(model, params, n_slots=2, capacity=CAP, mesh=mesh)
    prompt = np.asarray([[1, 2, 3], [4, 5, 6]], np.int32)
    step.prefill(prompt, [3, 3], [0, 1])
    assert not calls                     # prefill never takes the kernel
    logits = step.decode(jnp.asarray([7, 8], jnp.int32))
    assert len(calls) == (LAYERS if kernel else 0)
    toks, _ = step.decode_k(
        jnp.asarray([7, 8], jnp.int32), jax.random.split(
            jax.random.PRNGKey(0), 2), [0.0, 0.0], [0, 0], [-1, -1], [4, 4],
        [True, True], [0, 0], 2)
    assert len(calls) == (2 * LAYERS if kernel else 0)
    monkeypatch.setattr(transformer, "write_rows", pw.vmap_write_rows)
    plain = ServingStep(model, params, n_slots=2, capacity=CAP)
    plain.prefill(prompt, [3, 3], [0, 1])
    want = np.asarray(plain.decode(jnp.asarray([7, 8], jnp.int32)))
    if kernel:
        np.testing.assert_array_equal(np.asarray(logits), want)
    else:   # heads summed across devices: another order of the same sums
        np.testing.assert_allclose(np.asarray(logits), want, atol=1e-4)
