"""ZeRO-1 sharded optimizer tests.

Oracle (the reference suite's style, SURVEY.md §4): the sharded-optimizer
step must match the replicated-optimizer step bit-for-bit-ish (allclose) on
the same data — sharding the optimizer state is a memory layout choice, not
a numerics change.
"""

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu
from chainermn_tpu.models import MLP
from chainermn_tpu.optimizers import (
    fsdp_gather_params,
    make_fsdp_train_step,
    make_zero1_train_step,
    zero1_params,
)
from chainermn_tpu.training.step import make_data_parallel_train_step

from jax.sharding import NamedSharding, PartitionSpec as P

# numerics-heavy compile farm: covered nightly via the full run,
# excluded from the tier-1 wall-clock budget
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def comm():
    return chainermn_tpu.create_communicator("xla")


def _data(comm, batch_per=4, seed=0):
    n = comm.size * batch_per
    rs = np.random.RandomState(seed)
    x = rs.rand(n, 28, 28).astype(np.float32)
    y = rs.randint(0, 10, size=(n,)).astype(np.int32)
    dsh = NamedSharding(comm.mesh, P(comm.axis_names[0]))
    return jax.device_put(x, dsh), jax.device_put(y, dsh)


@pytest.mark.parametrize("opt_name", ["sgd", "adam"])
def test_zero1_matches_replicated(comm, opt_name):
    model = MLP(n_units=32, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]
    make_opt = {
        "sgd": lambda: optax.sgd(0.1, momentum=0.9),
        "adam": lambda: optax.adam(1e-2),
    }[opt_name]

    # replicated baseline
    ropt = chainermn_tpu.create_multi_node_optimizer(make_opt(), comm)
    rparams = comm.bcast_data(params)
    rstate = (rparams, jax.jit(ropt.init)(rparams))
    rstep = make_data_parallel_train_step(model, ropt, comm, donate=False)

    # zero-1
    zstep, zstate = make_zero1_train_step(model, make_opt(), comm, params,
                                          donate=False)

    x, y = _data(comm)
    for i in range(3):
        rstate, rm = rstep(rstate, x, y)
        zstate, zm = zstep(zstate, x, y)
        np.testing.assert_allclose(float(rm["main/loss"]),
                                   float(zm["main/loss"]), rtol=1e-5)

    got = zero1_params(zstate, params)
    want = rstate[0]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6),
        want, got,
    )


def test_zero1_opt_state_is_sharded(comm):
    model = MLP(n_units=32, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]
    step, state = make_zero1_train_step(model, optax.adam(1e-2), comm,
                                        params)
    p_shard, opt_state = state
    n = comm.size
    from chainermn_tpu.optimizers.zero import _padded_size

    flat = jax.flatten_util.ravel_pytree(params)[0]
    padded = _padded_size(flat.size, n)
    assert p_shard.shape == (padded,)
    # the vector is sharded over the axis: each device holds padded/n
    shard_sizes = {
        s.data.shape[0] for s in p_shard.addressable_shards
    }
    assert shard_sizes == {padded // n}
    # adam's mu/nu follow the shard
    mu = opt_state[0].mu
    assert mu.shape == (padded,)
    assert {s.data.shape[0] for s in mu.addressable_shards} == {padded // n}


@pytest.mark.parametrize("opt_name", ["sgd", "adam"])
def test_fsdp_matches_replicated(comm, opt_name):
    model = MLP(n_units=32, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]
    make_opt = {
        "sgd": lambda: optax.sgd(0.1, momentum=0.9),
        "adam": lambda: optax.adam(1e-2),
    }[opt_name]

    ropt = chainermn_tpu.create_multi_node_optimizer(make_opt(), comm)
    rparams = comm.bcast_data(params)
    rstate = (rparams, jax.jit(ropt.init)(rparams))
    rstep = make_data_parallel_train_step(model, ropt, comm, donate=False)

    fstep, fstate = make_fsdp_train_step(model, make_opt(), comm, params,
                                         donate=False)

    x, y = _data(comm)
    for i in range(3):
        rstate, rm = rstep(rstate, x, y)
        fstate, fm = fstep(fstate, x, y)
        np.testing.assert_allclose(float(rm["main/loss"]),
                                   float(fm["main/loss"]), rtol=1e-5)

    got = fsdp_gather_params(fstate)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6),
        rstate[0], got,
    )


def test_fsdp_params_and_opt_state_sharded(comm):
    model = MLP(n_units=32, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]
    step, state = make_fsdp_train_step(model, optax.adam(1e-2), comm, params)
    p, opt_state = state
    n = comm.size
    ax = comm.axis_name

    def sharded_leaves(tree):
        return [l for l in jax.tree_util.tree_leaves(tree)
                if any(d >= n and d % n == 0 for d in l.shape)]

    big = sharded_leaves(p)
    assert big, "expected shardable parameter leaves"
    for l in big:
        assert ax in tuple(l.sharding.spec), (l.shape, l.sharding)
        # each device holds 1/n of the leaf
        full = np.prod(l.shape)
        assert {int(np.prod(s.data.shape))
                for s in l.addressable_shards} == {full // n}
    # adam mu follows the param sharding
    mu_big = sharded_leaves(opt_state[0].mu)
    for l in mu_big:
        full = np.prod(l.shape)
        assert {int(np.prod(s.data.shape))
                for s in l.addressable_shards} == {full // n}


def test_zero1_padding_path(comm):
    # a model whose param count is NOT divisible by the axis size
    model = MLP(n_units=13, n_out=3)
    params = model.init(jax.random.PRNGKey(1),
                        np.zeros((2, 28, 28), np.float32))["params"]
    flat = jax.flatten_util.ravel_pytree(params)[0]
    assert flat.size % comm.size != 0, "want the padding path"
    step, state = make_zero1_train_step(model, optax.sgd(0.1), comm, params,
                                        donate=False)
    n = comm.size * 2
    rs = np.random.RandomState(0)
    x = rs.rand(n, 28, 28).astype(np.float32)
    y = rs.randint(0, 3, size=(n,)).astype(np.int32)
    state, m = step(state, x, y)
    assert np.isfinite(float(m["main/loss"]))
    got = zero1_params(state, params)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(params)


@pytest.mark.parametrize("bucket_kib", [8, 64])
def test_zero1_bucketed_matches_unbucketed(comm, bucket_kib):
    """bucket_bytes is a memory-layout choice, not a numerics change:
    losses match BITWISE and re-assembled params match the unbucketed
    step across several adam steps."""
    model = MLP(n_units=32, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]
    bb = bucket_kib * 1024
    s0, st0 = make_zero1_train_step(model, optax.adam(1e-2), comm, params,
                                    donate=False)
    s1, st1 = make_zero1_train_step(model, optax.adam(1e-2), comm, params,
                                    donate=False, bucket_bytes=bb)
    from chainermn_tpu.optimizers.zero import _BucketLayout

    n_buckets = len(_BucketLayout(params, comm.size, bb).buckets)
    assert n_buckets > 1, "config must exercise multiple buckets"

    x, y = _data(comm)
    for _ in range(3):
        st0, m0 = s0(st0, x, y)
        st1, m1 = s1(st1, x, y)
        assert float(m0["main/loss"]) == float(m1["main/loss"])

    p0 = zero1_params(st0, params)
    p1 = zero1_params(st1, params, bucket_bytes=bb)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6),
        p0, p1)


def test_zero1_params_layout_mismatch_raises(comm):
    """Reading a bucketed state without bucket_bytes (or vice versa)
    must raise, never silently permute (interleaved padding would
    corrupt every leaf after bucket 0)."""
    model = MLP(n_units=32, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]
    bb = 64 * 1024
    _, stb = make_zero1_train_step(model, optax.sgd(0.1), comm, params,
                                   donate=False, bucket_bytes=bb)
    with pytest.raises(ValueError, match="bucket"):
        zero1_params(stb, params)
    _, st = make_zero1_train_step(model, optax.sgd(0.1), comm, params,
                                  donate=False)
    with pytest.raises(ValueError, match="WITHOUT bucket_bytes"):
        zero1_params(st, params, bucket_bytes=bb)


def test_zero1_bucketed_kills_full_gradient_transient(comm):
    """THE ZeRO-1 memory claim, from the compiler's own buffer
    assignment: the bucketed step's temp allocation is smaller than the
    unbucketed step's by ≈ the model's full flat size — the transient
    full gradient (+ flat pack) no longer exists as live buffers."""
    model = MLP(n_units=512, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]
    flat_bytes = sum(
        l.size * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(params))
    x, y = _data(comm)

    temps = {}
    for bb in (None, 256 * 1024):
        s, st = make_zero1_train_step(model, optax.adam(1e-2), comm,
                                      params, donate=False,
                                      bucket_bytes=bb)
        compiled = jax.jit(lambda st, x, y: s(st, x, y)).lower(
            st, x, y).compile()
        ma = compiled.memory_analysis()
        if ma is None:
            pytest.skip("backend exposes no memory_analysis")
        temps[bb] = ma.temp_size_in_bytes

    saved = temps[None] - temps[256 * 1024]
    # the full padded gradient is one flat_bytes buffer; demand at least
    # 3/4 of it back (scheduling details may keep fractions alive)
    assert saved >= 0.75 * flat_bytes, (
        f"bucketing saved only {saved} of the {flat_bytes}-byte full "
        f"gradient (temps: {temps})")


def test_zero1_bucketed_jaxpr_scatters_per_bucket(comm):
    """Structural evidence: one psum_scatter PER BUCKET, operand sized
    to that bucket — never one full-model-size scatter."""
    model = MLP(n_units=64, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]
    bb = 64 * 1024
    from chainermn_tpu.optimizers.zero import _BucketLayout

    layout = _BucketLayout(params, comm.size, bb)
    s, st = make_zero1_train_step(model, optax.adam(1e-2), comm, params,
                                  donate=False, bucket_bytes=bb)
    x, y = _data(comm)
    jaxpr = jax.make_jaxpr(lambda st, x, y: s(st, x, y))(st, x, y)
    text = str(jaxpr)
    import re

    # psum_scatter lowers to `reduce_scatter` in the jaxpr; its OUTPUT
    # aval is the per-device shard of one bucket
    sizes = sorted(
        int(m.group(1))
        for m in re.finditer(
            r"f32\[(\d+)\][^=\n]*= reduce_scatter", text))
    assert sizes == sorted(layout.shard_lens), (sizes, layout.shard_lens)
    full_shard = sum(layout.shard_lens)
    assert full_shard not in sizes, "found a full-model-size scatter"


def test_zero2_bucketed_matches_zero2(comm):
    """Bucketed ZeRO-2 == plain ZeRO-2 on the same batch/microbatches
    (numerics unchanged; per-bucket scatter inside the scan), and its
    state layout matches bucketed ZeRO-1's so zero1_params decodes it."""
    from chainermn_tpu.optimizers.zero import (
        make_zero2_train_step,
        zero1_params,
    )

    bb = 16 * 1024
    model = MLP(n_units=24, n_out=4)
    n = comm.size
    rng = np.random.RandomState(5)
    x = rng.rand(4 * n, 28, 28).astype(np.float32)
    y = rng.randint(0, 4, (4 * n,)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), x[:2])["params"]
    s0, st0 = make_zero2_train_step(model, optax.adam(1e-2), comm, params,
                                    n_microbatches=2, donate=False)
    s1, st1 = make_zero2_train_step(model, optax.adam(1e-2), comm, params,
                                    n_microbatches=2, donate=False,
                                    bucket_bytes=bb)
    assert len(st1[0]) > 1, "config must exercise multiple buckets"
    dsh = NamedSharding(comm.mesh, P(comm.axis_names[0]))
    xg, yg = jax.device_put(x, dsh), jax.device_put(y, dsh)
    for _ in range(2):
        st0, m0 = s0(st0, xg, yg)
        st1, m1 = s1(st1, xg, yg)
        np.testing.assert_allclose(float(m0["main/loss"]),
                                   float(m1["main/loss"]), rtol=1e-6)
    p0 = zero1_params(st0, params)
    p1 = zero1_params(st1, params, bucket_bytes=bb)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6),
        p0, p1)


def _stacked_mlp_params(L=12, width=256, seed=3):
    """A depth-L MLP in scanned-stack form: {"inp", "blocks" [L,W,W],
    "out"} — the fsdp_scan_apply parameter layout."""
    rs = np.random.RandomState(seed)

    def w(*shape):
        return (rs.standard_normal(shape) * 0.05).astype(np.float32)

    return {"inp": jnp.asarray(w(784, width)),
            "blocks": {"w": jnp.asarray(w(L, width, width))},
            "out": jnp.asarray(w(width, 10))}


def _scan_loss(model, p, x, y, train=True, **kw):
    from chainermn_tpu.optimizers import fsdp_scan_apply

    h = x.reshape((x.shape[0], -1)) @ p["inp"]
    h = fsdp_scan_apply(lambda pi, h: jax.nn.relu(h @ pi["w"]),
                        p["blocks"], h)
    logits = h @ p["out"]
    import optax

    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits, y).mean()
    acc = (logits.argmax(-1) == y).mean()
    return loss, (acc, None)


def _loop_loss(model, p, x, y, train=True, **kw):
    """The same function as _scan_loss, layers unrolled in Python — the
    numerics oracle for the scan path."""
    import optax

    h = x.reshape((x.shape[0], -1)) @ p["inp"]
    for i in range(p["blocks"]["w"].shape[0]):
        h = jax.nn.relu(h @ p["blocks"]["w"][i])
    logits = h @ p["out"]
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits, y).mean()
    acc = (logits.argmax(-1) == y).mean()
    return loss, (acc, None)


def test_fsdp_scan_matches_replicated_loop(comm):
    """fsdp_scan_apply is a memory layout/schedule choice, not a
    numerics change: the scan-FSDP step matches the replicated
    data-parallel step running the unrolled Python loop."""
    import optax

    params = _stacked_mlp_params(L=6, width=64)

    ropt = chainermn_tpu.create_multi_node_optimizer(optax.adam(1e-2),
                                                     comm)
    rparams = comm.bcast_data(params)
    rstate = (rparams, jax.jit(ropt.init)(rparams))
    rstep = make_data_parallel_train_step(None, ropt, comm,
                                          loss_fn=_loop_loss,
                                          donate=False)

    fstep, fstate = make_fsdp_train_step(None, optax.adam(1e-2), comm,
                                         params, loss_fn=_scan_loss,
                                         donate=False)
    x, y = _data(comm)
    for _ in range(3):
        rstate, rm = rstep(rstate, x, y)
        fstate, fm = fstep(fstate, x, y)
        np.testing.assert_allclose(float(rm["main/loss"]),
                                   float(fm["main/loss"]), rtol=1e-5)
    got = fsdp_gather_params(fstate)
    # psum-of-grads (replicated) vs per-leaf reduce-scatter (FSDP) order
    # differences, amplified by three adam steps: atol ~5e-5 on 0.05-scale
    # weights (losses above match to 1e-5 every step)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=5e-5),
        rstate[0], got)


def test_fsdp_scan_bounds_gathered_param_memory(comm):
    """THE FSDP memory claim, from the compiler's own buffer assignment
    (VERDICT r4 #3, the analog of the bucketed-ZeRO-1 evidence): the
    scan-FSDP step's temp allocation is bounded by ≈ param-shard + a
    couple of layers — NOT the full parameter size. If the scan path
    degenerated to replicated-with-sharded-storage (all gathered layers
    co-live, which is exactly what the PLAIN fsdp step does on a
    memory-rich compile — measured 96 MB temp for this 51 MB model),
    temp would exceed full-param bytes and this fails."""
    L, width = 12, 1024
    params = _stacked_mlp_params(L=L, width=width)
    leaves = jax.tree_util.tree_leaves(params)
    full = sum(l.size * l.dtype.itemsize for l in leaves)
    largest = max(l.size * l.dtype.itemsize for l in leaves) // L
    shard = full // comm.size

    step, state = make_fsdp_train_step(None, optax.adam(1e-3), comm,
                                       params, loss_fn=_scan_loss,
                                       donate=False)
    x, y = _data(comm, batch_per=1)
    compiled = jax.jit(lambda st, x, y: step(st, x, y)).lower(
        state, x, y).compile()
    ma = compiled.memory_analysis()
    if ma is None:
        pytest.skip("backend exposes no memory_analysis")
    temp = ma.temp_size_in_bytes
    bound = shard + 2 * largest + 4 * 2 ** 20  # slack: activations etc.
    assert temp <= bound, (
        f"scan-FSDP temp {temp / 2**20:.1f} MB exceeds the per-layer "
        f"liveness bound {bound / 2**20:.1f} MB (full params "
        f"{full / 2**20:.1f} MB) — gathered layers are co-living")
    # and it is far below full-param size — the degeneration signature
    assert temp < 0.5 * full, (temp, full)


def test_fsdp_stack_shardings_never_shard_stack_dim(comm):
    """With L divisible by the axis size, plain fsdp_shardings would
    shard the scan dim; fsdp_stack_shardings must skip it, and the full
    step must run with the param_shardings override (opt state following
    the overridden shardings by shape)."""
    import optax

    from chainermn_tpu.optimizers import fsdp_shardings, fsdp_stack_shardings

    n = comm.size
    params = _stacked_mlp_params(L=2 * n, width=64)
    ax = comm.axis_name

    # a DECOY leaf with the SAME shape as the stack but the naive
    # sharding: opt-state matching must key on tree path, not shape —
    # shape-only matching would give one of the two mu leaves the other's
    # sharding (review finding, r5)
    params["decoy"] = {"w": jnp.zeros_like(params["blocks"]["w"])}

    naive = fsdp_shardings(params, comm)
    assert tuple(naive["blocks"]["w"].spec) == (ax,), (
        "precondition: the naive rule shards the stack dim here")
    stack = fsdp_stack_shardings(params, comm)
    sp = tuple(stack["blocks"]["w"].spec)
    assert sp[0] is None and ax in sp, sp

    shardings = dict(naive, blocks=stack["blocks"])
    step, state = make_fsdp_train_step(None, optax.adam(1e-3), comm,
                                       params, loss_fn=_scan_loss,
                                       donate=False,
                                       param_shardings=shardings)
    # adam's mu follows each leaf's OWN sharding, matched by tree path
    mu = state[1][0].mu
    assert tuple(mu["blocks"]["w"].sharding.spec) == sp
    assert tuple(mu["decoy"]["w"].sharding.spec) == (ax,), (
        "decoy mu must keep the naive sharding, not inherit the stack "
        "override through a shape collision")
    x, y = _data(comm, batch_per=1)
    state, m = step(state, x, y)
    assert np.isfinite(float(m["main/loss"]))


def test_fsdp_warns_on_stacked_tree_without_override(comm):
    """A params tree that looks like a scanned layer stack (sibling
    leaves sharing a leading dim divisible by comm.size) must raise a
    UserWarning when no param_shardings override is given — the default
    first-divisible-dim rule shards the LAYER dim, silently defeating
    fsdp_scan_apply's per-layer liveness bound — and must stay silent
    once the stack shardings are passed."""
    import warnings

    from chainermn_tpu.optimizers import fsdp_shardings, fsdp_stack_shardings

    n = comm.size
    L, width = 2 * n, 32
    rs = np.random.RandomState(0)

    def w(*shape):
        return jnp.asarray((rs.standard_normal(shape) * 0.05)
                           .astype(np.float32))

    params = {"inp": w(784, width),
              "blocks": {"w": w(L, width, width),
                         "b": jnp.zeros((L, width), jnp.float32)},
              "out": w(width, 10)}

    def loss(model, p, x, y, train=True, **kw):
        from chainermn_tpu.optimizers import fsdp_scan_apply

        h = x.reshape((x.shape[0], -1)) @ p["inp"]
        h = fsdp_scan_apply(
            lambda pi, h: jax.nn.relu(h @ pi["w"] + pi["b"]), p["blocks"], h)
        logits = h @ p["out"]
        l = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        return l, ((logits.argmax(-1) == y).mean(), None)

    with pytest.warns(UserWarning, match="scanned layer stack"):
        make_fsdp_train_step(None, optax.adam(1e-3), comm, params,
                             loss_fn=loss, donate=False)

    shardings = dict(fsdp_shardings(params, comm),
                     blocks=fsdp_stack_shardings(params, comm)["blocks"])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        step, state = make_fsdp_train_step(None, optax.adam(1e-3), comm,
                                           params, loss_fn=loss,
                                           donate=False,
                                           param_shardings=shardings)
    assert not [c for c in caught if "layer stack" in str(c.message)], caught
    x, y = _data(comm, batch_per=1)
    state, m = step(state, x, y)
    assert np.isfinite(float(m["main/loss"]))


import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))
from lm_scan_helpers import lm_scan_setup as _lm_scan_setup  # noqa: E402
from lm_scan_helpers import tiny_lm as _tiny_lm  # noqa: E402


def test_lm_fsdp_scan_matches_replicated(comm):
    """The FLAGSHIP integration of the scan-FSDP memory bound: a
    TransformerLM trained through stack_lm_blocks +
    make_lm_fsdp_scan_loss matches the replicated data-parallel step
    with fused_lm_loss — the piecewise-submodule forward IS
    model.apply's numerics, and unstacked gathered params line up."""
    import optax

    from chainermn_tpu.models.transformer import (lm_loss_with_aux,
                                                  unstack_lm_blocks)

    model = _tiny_lm()
    n = comm.size
    rs = np.random.RandomState(0)
    toks = rs.randint(0, 2048, size=(2 * n, 17)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), toks[:1, :-1])["params"]

    # baseline: the UNFUSED XLA loss — the comparison then also
    # cross-validates the fused-CE kernel against XLA's CE
    ropt = chainermn_tpu.create_multi_node_optimizer(optax.adam(1e-2),
                                                     comm)
    rparams = comm.bcast_data(params)
    rstate = (rparams, jax.jit(ropt.init)(rparams))
    rstep = make_data_parallel_train_step(model, ropt, comm,
                                          loss_fn=lm_loss_with_aux,
                                          donate=False)

    fstep, fstate = _lm_scan_setup(comm, model, params, optax.adam(1e-2))

    dsh = NamedSharding(comm.mesh, P(comm.axis_names[0]))
    x = jax.device_put(toks[:, :-1], dsh)
    y = jax.device_put(toks[:, 1:], dsh)
    for _ in range(3):
        rstate, rm = rstep(rstate, x, y)
        fstate, fm = fstep(fstate, x, y)
        np.testing.assert_allclose(float(rm["main/loss"]),
                                   float(fm["main/loss"]), rtol=2e-5)
        np.testing.assert_allclose(float(rm["main/accuracy"]),
                                   float(fm["main/accuracy"]), rtol=2e-5)

    got = unstack_lm_blocks(fsdp_gather_params(fstate))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=5e-5),
        rstate[0], got)


def test_lm_fsdp_scan_memory_bound(comm):
    """The flagship path inherits the scan's compiled memory bound: temp
    allocation stays well under full-param bytes (a degenerate
    all-layers-gathered schedule would exceed it)."""
    import optax

    from chainermn_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab=2048, d_model=256, n_heads=4, n_layers=8,
                          d_ff=1024, max_len=32, pos_emb="rope",
                          attention="reference")
    rs = np.random.RandomState(1)
    toks = rs.randint(0, 2048, size=(comm.size, 33)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), toks[:1, :-1])["params"]
    full = sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(params))

    step, state = _lm_scan_setup(comm, model, params, optax.adam(1e-3))
    dsh = NamedSharding(comm.mesh, P(comm.axis_names[0]))
    x = jax.device_put(toks[:, :-1], dsh)
    y = jax.device_put(toks[:, 1:], dsh)
    compiled = jax.jit(lambda st, x, y: step(st, x, y)).lower(
        state, x, y).compile()
    ma = compiled.memory_analysis()
    if ma is None:
        pytest.skip("backend exposes no memory_analysis")
    assert ma.temp_size_in_bytes < 0.6 * full, (
        f"temp {ma.temp_size_in_bytes / 2**20:.1f} MB vs full params "
        f"{full / 2**20:.1f} MB — gathered layers co-living")
    state, m = step(state, x, y)
    assert np.isfinite(float(m["main/loss"]))


def test_stack_unstack_lm_blocks_roundtrip(comm):
    from chainermn_tpu.models.transformer import (stack_lm_blocks,
                                                  unstack_lm_blocks)

    model = _tiny_lm()
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    back = unstack_lm_blocks(stack_lm_blocks(params))
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        params, back)


def _structure_dependent_opts(params):
    """Optimizers whose update depends on parameter-tree structure — the
    flat ZeRO layouts would silently mis-train every one of these."""
    import optax

    return {
        "lamb": optax.lamb(1e-3),  # per-layer trust ratio
        "lars": optax.lars(0.1),
        "masked_wd": optax.adamw(  # ndim-keyed weight-decay mask
            1e-3, mask=jax.tree_util.tree_map(lambda l: l.ndim > 1,
                                              params)),
        "multi_transform": optax.multi_transform(
            {"a": optax.sgd(0.1), "b": optax.adam(1e-3)},
            jax.tree_util.tree_map(lambda l: "a" if l.ndim > 1 else "b",
                                   params)),
        # whole-tree reduction: each ZeRO shard would clip by its OWN
        # shard's norm instead of the global norm
        "clip_global_norm": optax.chain(optax.clip_by_global_norm(1.0),
                                        optax.adam(1e-3)),
    }


def test_zero_flat_refuses_structure_dependent_optimizers(comm):
    """make_zero1/2_train_step must REFUSE (not silently mis-train)
    optimizers whose update is not element-wise: the init-time probe
    compares a tree update against a flat-packed update and raises on
    mismatch (VERDICT r4 #4)."""
    from chainermn_tpu.optimizers.zero import make_zero2_train_step

    model = MLP(n_units=16, n_out=4)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]
    for name, opt in _structure_dependent_opts(params).items():
        with pytest.raises(ValueError, match="element-wise"):
            make_zero1_train_step(model, opt, comm, params)
        with pytest.raises(ValueError, match="element-wise"):
            make_zero1_train_step(model, opt, comm, params,
                                  bucket_bytes=16 * 1024)
        with pytest.raises(ValueError, match="element-wise"):
            make_zero2_train_step(model, opt, comm, params,
                                  n_microbatches=2)


def test_zero_flat_probe_admits_elementwise_optimizers(comm):
    """The probe is semantic, not a blocklist: element-wise transforms
    build, including chained ones. (clip_by_global_norm is REFUSED — see
    _structure_dependent_opts — because ZeRO's update runs per-shard and
    each shard would clip by its own norm.)"""
    import optax

    model = MLP(n_units=16, n_out=10)  # _data labels are [0, 10)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]
    for opt in (
        optax.sgd(0.1, momentum=0.9),
        optax.adamw(1e-3, weight_decay=1e-2),
        optax.chain(optax.clip(0.5), optax.adam(1e-3)),
    ):
        step, state = make_zero1_train_step(model, opt, comm, params,
                                            donate=False)
        x, y = _data(comm, batch_per=1)
        state, m = step(state, x, y)
        assert np.isfinite(float(m["main/loss"]))


def test_fsdp_accepts_structure_dependent_optimizers(comm):
    """The guidance in the refusal error is real: FSDP (per-leaf
    sharding) trains the same optimizers the flat layouts refuse, and
    matches the replicated step on LAMB — per-layer trust ratios need
    per-leaf structure, which FSDP preserves."""
    import optax

    model = MLP(n_units=16, n_out=10)  # _data labels are [0, 10)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]

    ropt = chainermn_tpu.create_multi_node_optimizer(optax.lamb(1e-3),
                                                     comm)
    rparams = comm.bcast_data(params)
    rstate = (rparams, jax.jit(ropt.init)(rparams))
    rstep = make_data_parallel_train_step(model, ropt, comm, donate=False)

    fstep, fstate = make_fsdp_train_step(model, optax.lamb(1e-3), comm,
                                         params, donate=False)
    x, y = _data(comm)
    for _ in range(2):
        rstate, rm = rstep(rstate, x, y)
        fstate, fm = fstep(fstate, x, y)
        np.testing.assert_allclose(float(rm["main/loss"]),
                                   float(fm["main/loss"]), rtol=1e-5)
    got = fsdp_gather_params(fstate)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6),
        rstate[0], got)


def test_zero2_matches_zero1(comm):
    """One ZeRO-2 step (2 microbatches) == one ZeRO-1 step on the same
    global batch: grad-of-mean equals mean-of-microbatch-grads, so the
    updated parameters must agree to fp tolerance; state stays sharded."""
    import optax

    from chainermn_tpu.models import MLP
    from chainermn_tpu.optimizers.zero import (
        make_zero1_train_step,
        make_zero2_train_step,
        zero1_params,
    )

    n = comm.size
    model = MLP(n_units=16, n_out=4)
    rng = np.random.RandomState(0)
    x = rng.rand(4 * n, 28, 28).astype(np.float32)
    y = rng.randint(0, 4, (4 * n,)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), x[:2])["params"]

    s1, st1 = make_zero1_train_step(model, optax.adam(1e-2), comm, params)
    s2, st2 = make_zero2_train_step(model, optax.adam(1e-2), comm, params,
                                    n_microbatches=2)
    dsh = NamedSharding(comm.mesh, P(comm.axis_names[0]))
    xg, yg = jax.device_put(x, dsh), jax.device_put(y, dsh)

    st1, m1 = s1(st1, xg, yg)
    st2, m2 = s2(st2, xg, yg)
    np.testing.assert_allclose(float(m1["main/loss"]),
                               float(m2["main/loss"]), rtol=1e-5)
    p1 = zero1_params(st1, params)
    p2 = zero1_params(st2, params)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)
    # accumulator/optimizer memory is sharded: leading dim of the m/v
    # leaves is padded_total/n per device
    shard = st2[0]
    assert shard.sharding.spec == P(comm.axis_names[0])
