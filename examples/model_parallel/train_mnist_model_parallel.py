#!/usr/bin/env python
"""Model-parallel MNIST via MultiNodeChainList (BASELINE config #5).

Reference: the model-parallel MNIST variants under examples/ — an MLP split
across ranks with chainermn.functions.send/recv edges. Here the whole stage
graph is declared once and compiles into a single program whose inter-stage
edges are XLA collective-permutes; backward retraces them in reverse
automatically.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import chainermn_tpu

from jax import shard_map
from jax.sharding import PartitionSpec as P
from chainermn_tpu.datasets.toy import synthetic_mnist
from chainermn_tpu.iterators import SerialIterator
from chainermn_tpu.links import MultiNodeChainList


class Block(nn.Module):
    feat: int
    act: bool = True

    @nn.compact
    def __call__(self, x):
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.feat)(x)
        return nn.relu(x) if self.act else x


def main():
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: model-parallel MNIST")
    p.add_argument("--batchsize", "-b", type=int, default=256)
    p.add_argument("--epoch", "-e", type=int, default=2)
    p.add_argument("--unit", "-u", type=int, default=200)
    p.add_argument("--stages", type=int, default=4)
    args = p.parse_args()

    comm = chainermn_tpu.create_communicator("xla")
    n_stages = min(args.stages, comm.size)
    if comm.is_master:
        print(f"devices: {comm.size}  pipeline stages: {n_stages}")

    chain = MultiNodeChainList(comm)
    for s in range(n_stages):
        last = s == n_stages - 1
        chain.add_link(
            Block(10 if last else args.unit, act=not last),
            rank=s,
            rank_in=None if s == 0 else s - 1,
            rank_out=None if last else s + 1,
        )

    train = synthetic_mnist(2048, seed=0)
    x0 = np.stack([train[i][0] for i in range(2)])
    params = chain.init(jax.random.PRNGKey(0), jnp.asarray(x0))

    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    def loss_fn(params, x, y):
        def f(x):
            return chain.apply(params, x)

        logits = shard_map(f, mesh=comm.mesh, in_specs=(P(),),
                           out_specs=P())(x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    @jax.jit
    def step(params, opt_state, x, y):
        loss, g = jax.value_and_grad(loss_fn)(params, x, y)
        updates, opt_state = opt.update(g, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    it = SerialIterator(train, args.batchsize, shuffle=True, seed=0)
    i = 0
    while it.epoch < args.epoch:
        batch = it.next()
        x = jnp.asarray(np.stack([b[0] for b in batch]))
        y = jnp.asarray(np.stack([b[1] for b in batch]))
        params, opt_state, loss = step(params, opt_state, x, y)
        i += 1
        if comm.is_master and i % 8 == 0:
            print(f"epoch {it.epoch} iter {i} loss {float(loss):.4f}",
                  flush=True)
    if comm.is_master:
        print(f"final loss: {float(loss):.4f}")
    return float(loss)


if __name__ == "__main__":
    # supervisor exit-status contract (docs/fault_tolerance.md):
    # 0 clean, 143 preempted-and-checkpointed, 75 watchdog abort
    from chainermn_tpu.resilience.supervisor import main_exit_code
    sys.exit(main_exit_code(main))
