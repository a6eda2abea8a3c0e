"""The updater's and the trainer's spans: under a profiler session every
``update()`` gives ``updater.input`` then ``updater.dispatch`` inside
``updater.update`` and every extension fired its own span; without a session
nothing is recorded and the parameters come out bit-equal."""
import numpy as np
import pytest

import jax
import optax

import chainermn_tpu
from chainermn_tpu import tracing
from chainermn_tpu.datasets.toy import synthetic_mnist
from chainermn_tpu.iterators import SerialIterator
from chainermn_tpu.models import MLP
from chainermn_tpu.training import StandardUpdater, Trainer
from chainermn_tpu.training.step import make_data_parallel_train_step

STEPS, BATCH = 6, 64


def _run():
    comm = chainermn_tpu.create_communicator("xla")
    train = synthetic_mnist(256, seed=0)
    model = MLP(n_units=16, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]
    opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
    step = make_data_parallel_train_step(model, opt, comm)
    updater = StandardUpdater(
        SerialIterator(train, BATCH, shuffle=False), step,
        (comm.bcast_data(params), opt.init(params)), comm)
    trainer = Trainer(updater, stop_trigger=(STEPS, "iteration"))

    class Probe:
        def __call__(self, t):
            pass

    trainer.extend(Probe(), trigger=(2, "iteration"))
    trainer.extend(lambda t: None, trigger=(3, "iteration"), name="thirds")
    trainer.run()
    return step, jax.device_get(updater.state[0])


def test_trained_without_a_session_nothing_is_recorded():
    tracing.clear()
    _run()
    assert tracing.rows() == []


@pytest.fixture(scope="module")
def traced(profiler_session):
    plain = _run()[1]
    tracing.clear()
    with profiler_session():
        step, params = _run()
    rows = tracing.rows()
    tracing.clear()
    return {"rows": rows, "plain": plain, "params": params, "step": step}


def test_a_session_changes_no_parameter_and_compiles_one_step(traced):
    for a, b in zip(jax.tree_util.tree_leaves(traced["plain"]),
                    jax.tree_util.tree_leaves(traced["params"])):
        np.testing.assert_array_equal(a, b)
    assert traced["step"]._cache_size() == 1


def test_every_update_holds_input_then_dispatch(traced):
    rows = traced["rows"]
    updates = [r for r in rows if r.name == "updater.update"]
    assert [u.attrs["iteration"] for u in updates] == list(range(STEPS))
    for u in updates:
        kids = [r for r in rows if r.parent_id == u.id]
        assert [k.name for k in kids] == ["updater.input",
                                          "updater.dispatch"]
        feed, dispatch = kids
        assert u.t0 <= feed.t0 <= feed.t1 <= dispatch.t0 <= dispatch.t1 <= u.t1
        assert (feed.t1 - feed.t0) + (dispatch.t1 - dispatch.t0) <= u.t1 - u.t0
        # the converter's arrays: 64 images of 28 x 28 f32 and 64 labels
        assert feed.attrs["bytes"] >= BATCH * 28 * 28 * 4


def test_every_extension_fired_is_a_span_with_its_name(traced):
    fired = [r for r in traced["rows"] if r.name == "trainer.extension"]
    assert all(r.parent_id is None for r in fired)
    assert sorted(r.attrs["name"] for r in fired) == (
        ["Probe"] * 3 + ["thirds"] * 2)


def test_the_step_names_its_optimizer_and_its_gradient_reduction():
    """Device scopes are metadata of the compiled step: the optimizer's
    update under ``optimizer_update``, and inside it the reduction of the
    gradients and its scaling under ``grad_reduce``."""
    comm = chainermn_tpu.create_communicator("xla")
    model = MLP(n_units=16, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]
    opt = chainermn_tpu.create_multi_node_optimizer(optax.adam(1e-3), comm)
    step = make_data_parallel_train_step(model, opt, comm)
    x = np.zeros((comm.size * 2, 28, 28), np.float32)
    y = np.zeros((comm.size * 2,), np.int32)
    text = step.lower((params, opt.init(params)), x, y).as_text(
        debug_info=True)
    assert "optimizer_update/grad_reduce/" in text
    assert any("optimizer_update/" in l and "grad_reduce" not in l
               for l in text.splitlines())


# -- lifecycle spans and the compile log: kept without a session ---------
def test_the_factory_is_a_step_build_and_the_first_update_a_first_call():
    import time

    t0 = time.perf_counter()
    step, _ = _run()
    rows = tracing.lifecycle_rows(t0)
    assert [r.name for r in rows] == ["step.build", "program.first_call"]
    build, call = rows
    assert build.attrs == {} and build.parent_id is None
    assert call.attrs == {"program": "local_step"}
    assert step._cache_size() == 1
    steps = [c for c in tracing.compiles(t0)
             if c.fun_name == "jit(local_step)"]
    assert len(steps) == 1 and call.t0 <= steps[0].t_end <= call.t1
    (entry,) = [e for e in tracing.compile_table(t0)
                if e["span"] == "program.first_call"]
    assert (entry["program"], entry["fun_name"]) == ("local_step",
                                                     "jit(local_step)")
    assert 0 <= entry["first_run_s"] < entry["span_s"]
    assert tracing.rows() == []


def test_a_step_built_on_its_first_state_says_how_large_the_state_is():
    """A stateful gradient reducer's step is made when the state's structure
    is known: its ``step.build`` carries the bytes, inside the updater's
    first call."""
    import time

    from chainermn_tpu.collectives.quantized import QuantizedReducer

    comm = chainermn_tpu.create_communicator("xla")
    model = MLP(n_units=16, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1), comm,
        grad_reducer=QuantizedReducer(comm, mode="int8", ef=True))
    t0 = time.perf_counter()
    step = make_data_parallel_train_step(model, opt, comm)
    assert tracing.lifecycle_rows(t0) == []     # nothing to build yet
    params = comm.bcast_data(params)
    state = (params, jax.jit(opt.init)(params))
    n_params = sum(l.nbytes for l in jax.tree_util.tree_leaves(state[0]))
    updater = StandardUpdater(
        SerialIterator(synthetic_mnist(128, seed=0), BATCH, shuffle=False),
        step, state, comm)
    t1 = time.perf_counter()
    updater.update()
    updater.update()
    float(updater.last_metrics["main/loss"])
    build, call = tracing.lifecycle_rows(t1)
    assert (build.name, call.name) == ("step.build", "program.first_call")
    assert build.parent_id == call.id
    assert build.attrs["param_bytes"] == n_params
    assert build.attrs["opt_state_bytes"] > 0
