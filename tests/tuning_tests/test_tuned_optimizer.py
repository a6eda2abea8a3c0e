"""End-to-end: a schedtune plan flows DB -> optimizer -> reducer, and
the tuned schedule is a pure REORDERING — gradients bitwise-identical
to the untuned flat path on integer-valued floats (sums exactly
representable: any difference is a logic bug, not reassociation).
"""

import os

import jax
import numpy as np
import optax
import pytest

from jax import shard_map
from jax.sharding import PartitionSpec as P

import chainermn_tpu
from chainermn_tpu.collectives import (
    AutoReducer,
    make_grad_reducer,
    measure_strategies,
)
from chainermn_tpu.training.reports import TuningReport
from chainermn_tpu.tuning import (
    ProfileDB,
    SchedulePlan,
    Topology,
    tune_canned,
)

GRAD_BYTES = 51 << 20


@pytest.fixture(scope="module")
def comm():
    return chainermn_tpu.create_communicator("xla")


@pytest.fixture(scope="module")
def tuned_db_path(comm, tmp_path_factory):
    """A real schedtune artifact for THIS mesh's fingerprint."""
    res = tune_canned(Topology.from_comm(comm), GRAD_BYTES)
    assert res.improves_overlap
    p = str(tmp_path_factory.mktemp("schedtune") / "db.json")
    db = ProfileDB(p)
    db.put_plan(res.plan)
    db.save()
    return p


def _int_grads(comm, seed=0):
    """Integer-valued f32 pytree, ragged enough to split buckets."""
    rs = np.random.RandomState(seed)

    def leaf(*shape):
        return rs.randint(-8, 8, (comm.size,) + shape).astype(np.float32)

    return {"dense": {"kernel": leaf(257, 33), "bias": leaf(33)},
            "head": {"kernel": leaf(33, 11), "bias": leaf(11)}}


def _reduce(comm, reducer, grads):
    ax = comm.axis_names[0]

    def f(g):
        g = jax.tree_util.tree_map(lambda l: l[0], g)
        red, _ = reducer.reduce(g, ())
        return jax.tree_util.tree_map(lambda l: l[None], red)

    return jax.jit(shard_map(f, mesh=comm.mesh, in_specs=P(ax),
                             out_specs=P(ax)))(grads)


def _assert_trees_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)), a, b)


# ---------------------------------------------------------------------------
# the tier-1 bitwise acceptance test
# ---------------------------------------------------------------------------

def test_tuned_optimizer_bitwise_equal_to_flat(comm, tuned_db_path):
    tuned = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(1.0), comm, tune=tuned_db_path)
    flat = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(1.0), comm, grad_reducer="flat")
    assert tuned.plan is not None
    assert tuned.plan.fingerprint == Topology.from_comm(
        comm).fingerprint()
    grads = _int_grads(comm)
    _assert_trees_equal(_reduce(comm, tuned.grad_reducer, grads),
                        _reduce(comm, flat.grad_reducer, grads))


def test_tune_accepts_a_plan_object_directly(comm):
    plan = SchedulePlan(
        fingerprint=Topology.from_comm(comm).fingerprint(),
        model_key="default", strategy="flat", bucket_bytes=1 << 16,
        bucket_order="size")
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(1.0), comm, tune=plan)
    assert opt.plan is plan
    assert opt.grad_reducer.bucket_bytes == 1 << 16
    assert opt.grad_reducer.bucket_order == "size"
    grads = _int_grads(comm, seed=1)
    flat = make_grad_reducer("flat", comm)
    _assert_trees_equal(_reduce(comm, opt.grad_reducer, grads),
                        _reduce(comm, flat, grads))


def test_untuned_optimizer_has_no_plan(comm):
    # legacy contract: no reducer + no tune -> plain optax transform;
    # consumers probe the plan with getattr
    opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(1.0), comm)
    assert getattr(opt, "plan", None) is None
    with_reducer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(1.0), comm, grad_reducer="flat")
    assert with_reducer.plan is None


def test_explicit_reducer_wins_over_the_plan(comm, tuned_db_path):
    mine = make_grad_reducer("flat", comm, bucket_bytes=1 << 18)
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(1.0), comm, grad_reducer=mine, tune=tuned_db_path)
    assert opt.grad_reducer is mine
    assert opt.plan is not None  # still surfaced for reports


def test_stale_fingerprint_refused(comm):
    plan = SchedulePlan(
        fingerprint="tpu:v5e/ici:4+dcn:64", model_key="default",
        strategy="hierarchical", bucket_bytes=4 << 20)
    with pytest.raises(ValueError, match="stale schedule profile"):
        chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(1.0), comm, tune=plan)


def test_missing_profile_entry_refused(comm, tmp_path):
    empty = str(tmp_path / "empty.json")
    with pytest.raises(ValueError, match="no tuned schedule"):
        chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(1.0), comm, tune=empty)


def test_size_order_flat_reducer_bitwise_equal_to_default(comm):
    """bucket_order='size' repacks buckets; the summed result must not
    move by a single bit."""
    grads = _int_grads(comm, seed=2)
    default = make_grad_reducer("flat", comm)
    sized = make_grad_reducer("flat", comm, bucket_bytes=1 << 12,
                              bucket_order="size")
    _assert_trees_equal(_reduce(comm, default, grads),
                        _reduce(comm, sized, grads))


def test_bad_bucket_order_rejected(comm):
    with pytest.raises(ValueError):
        make_grad_reducer("flat", comm, bucket_order="alphabetical")


# ---------------------------------------------------------------------------
# AutoReducer profile consumption + honest-null persistence
# ---------------------------------------------------------------------------

def test_auto_reducer_reads_persisted_sweep(comm, tmp_path):
    p = str(tmp_path / "db.json")
    topo = Topology.from_comm(comm)
    db = ProfileDB(p)
    db.put_measured(topo, {("flat", 4 << 20): 111.0})
    db.save()
    ar = AutoReducer(comm, profile=p)
    assert ar.measured[("flat", 4 << 20)] == 111.0
    assert ar._estimate("flat", 4 << 20) == 111.0
    # an explicit measured= entry wins over the persisted one
    ar2 = AutoReducer(comm, profile=p,
                      measured={("flat", 4 << 20): 55.0})
    assert ar2._estimate("flat", 4 << 20) == 55.0


def test_measure_strategies_off_tpu_persists_nothing(comm, tmp_path):
    p = str(tmp_path / "db.json")
    out = measure_strategies(comm, sizes=(1 << 12,), db=p)
    assert out == {}  # honest null off TPU...
    assert not os.path.exists(p)  # ...and the null is never written


# ---------------------------------------------------------------------------
# TuningReport
# ---------------------------------------------------------------------------

class _FakeTrainer:
    def __init__(self):
        self.observation = {}


def test_tuning_report_surfaces_plan_observations(comm, tuned_db_path):
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(1.0), comm, tune=tuned_db_path)
    rep = TuningReport(opt, quiet=True)  # accepts the optimizer itself
    tr = _FakeTrainer()
    rep(tr)
    assert tr.observation["tuning/overlap_frac"] == \
        opt.plan.overlap_fraction
    assert tr.observation["tuning/bucket_bytes"] == opt.plan.bucket_bytes
    assert tr.observation["tuning/strategy"] == opt.plan.strategy


def test_tuning_report_noop_without_plan():
    tr = _FakeTrainer()
    TuningReport(None)(tr)
    assert tr.observation == {}
