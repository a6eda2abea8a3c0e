"""Native prefetch loader wired into the training loop (VERDICT r1 #3).

The reference's examples pay iterator.next() + concat + to_gpu on the host
every step (SURVEY.md §3.1); here the native C++ double-buffered gather
assembles batches off-thread and the uint8→float decode runs on device
inside the compiled step. These tests pin the functional wiring:
mmap'd uint8 file → PrefetchingLoader → StandardUpdater → convergence.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu
from chainermn_tpu.models import MLP
from chainermn_tpu.training import StandardUpdater, Trainer
from chainermn_tpu.training.loader import PrefetchingLoader
from chainermn_tpu.training.step import (
    classifier_loss,
    make_data_parallel_train_step,
)


@pytest.fixture(scope="module")
def comm():
    return chainermn_tpu.create_communicator("xla")


def _u8_dataset(tmp_path, n=256):
    """Learnable uint8 classification set, saved as mmap-able .npy."""
    rs = np.random.RandomState(0)
    ys = rs.randint(0, 4, size=n).astype(np.int32)
    protos = rs.randint(0, 256, (4, 28, 28), dtype=np.uint8)
    xs = np.clip(protos[ys].astype(np.int32)
                 + rs.randint(-8, 8, (n, 28, 28)), 0, 255).astype(np.uint8)
    xp, yp = str(tmp_path / "x.npy"), str(tmp_path / "y.npy")
    np.save(xp, xs)
    np.save(yp, ys)
    return xp, yp


def test_mmap_uint8_loader_trains_to_convergence(comm, tmp_path):
    xp, yp = _u8_dataset(tmp_path)
    xs = np.load(xp, mmap_mode="r")
    ys = np.load(yp, mmap_mode="r")
    assert isinstance(xs, np.memmap)

    model = MLP(n_units=32, n_out=4)

    def u8_loss(model, params, x, y, **kw):
        x = x.astype(jnp.float32) / 255.0
        return classifier_loss(model, params, x, y, **kw)

    params = comm.bcast_data(model.init(
        jax.random.PRNGKey(0), np.zeros((2, 28, 28), np.float32))["params"])
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(1e-2), comm)
    state = (params, jax.jit(opt.init)(params))
    step = make_data_parallel_train_step(model, opt, comm, loss_fn=u8_loss)

    B = 8 * comm.size
    loader = PrefetchingLoader(xs, ys, B, shuffle=True, seed=0)
    updater = StandardUpdater(loader, step, state, comm,
                              converter=lambda b: b)
    accs = []
    for _ in range(60):
        updater.update()
        accs.append(float(updater.last_metrics["main/accuracy"]))
    loader.close()
    assert np.mean(accs[-10:]) > 0.9, accs[-10:]
    # epoch bookkeeping advanced through the prefetch queue correctly
    assert updater.epoch == loader.epoch >= 1


def test_loader_epoch_matches_delivered_batches(comm, tmp_path):
    xp, yp = _u8_dataset(tmp_path, n=64)
    xs, ys = np.load(xp, mmap_mode="r"), np.load(yp, mmap_mode="r")
    loader = PrefetchingLoader(xs, ys, 16, shuffle=False, epochs=2)
    seen = 0
    for xb, yb in loader:
        assert xb.dtype == np.uint8 and xb.shape == (16, 28, 28)
        seen += 1
    loader.close()
    assert seen == 8  # 4 batches/epoch x 2 epochs
    assert loader.epoch == 2
