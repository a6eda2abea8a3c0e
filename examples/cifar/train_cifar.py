#!/usr/bin/env python
"""CIFAR-100 ResNet with MultiNodeBatchNormalization (BASELINE config #3).

Every BN layer's batch statistics span all replicas — the reference's
MultiNodeBatchNormalization path — by passing the communicator into the
model. Useful when the per-replica batch is small enough that local BN
statistics get noisy (the regime the reference built this link for).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import numpy as np
import optax

import chainermn_tpu
from chainermn_tpu.datasets.standard_formats import load_cifar
from chainermn_tpu.iterators import SerialIterator
from chainermn_tpu.models.resnet import CifarResNet
from chainermn_tpu.training import LogReport, PrintReport, StandardUpdater, Trainer
from chainermn_tpu.training.step import make_data_parallel_train_step


def main():
    p = argparse.ArgumentParser(description="ChainerMN-TPU example: CIFAR-100")
    p.add_argument("--batchsize", "-b", type=int, default=256)
    p.add_argument("--epoch", "-e", type=int, default=3)
    p.add_argument("--depth", type=int, default=20)
    p.add_argument("--communicator", type=str, default="xla")
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--n-train", type=int, default=4096)
    p.add_argument("--no-multi-node-bn", action="store_true",
                   help="use per-replica BN statistics instead")
    p.add_argument("--data-dir", default=None, metavar="DIR",
                   help="CIFAR binary-layout directory (train.bin for "
                        "CIFAR-100). Default: generate a local binary "
                        "dataset under --out and parse THAT — the "
                        "executed input path is always the real-format "
                        "parser")
    p.add_argument("--out", "-o", default="result")
    args = p.parse_args()

    comm = chainermn_tpu.create_communicator(args.communicator)
    if comm.is_master:
        print(f"devices: {comm.size}  multi-node BN: "
              f"{not args.no_multi_node_bn}")

    # real-format input path: parse CIFAR binary batches, generating them
    # locally first when no directory was given. Root-only build; samples
    # ship over the object plane.
    if comm.inter_rank == 0:
        data_dir = args.data_dir
        if data_dir is None:
            data_dir = os.path.join(args.out, "cifar-data")
            if not os.path.exists(os.path.join(data_dir, "train.bin")):
                from make_cifar_dataset import synth_uint8
                from chainermn_tpu.datasets.standard_formats import (
                    save_cifar)

                xs, ys = synth_uint8(args.n_train, 100, seed=0)
                save_cifar(data_dir, xs, ys, n_classes=100, train=True)
        train = load_cifar(data_dir, n_classes=100, train=True)
    else:
        train = None
    train = chainermn_tpu.scatter_dataset(train, comm, shuffle=True, seed=0,
                                          shared_storage=False)

    model = CifarResNet(
        num_classes=100, depth=args.depth,
        comm=None if args.no_multi_node_bn else comm,
    )
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((2, 32, 32, 3), np.float32))
    params = comm.bcast_data(variables["params"])
    batch_stats = comm.bcast_data(variables["batch_stats"])

    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(args.lr, momentum=0.9), comm
    )
    state = (params, optimizer.init(params), {"batch_stats": batch_stats})
    step = make_data_parallel_train_step(
        model, optimizer, comm, mutable=("batch_stats",)
    )

    it = SerialIterator(train, args.batchsize, shuffle=True, seed=0)
    updater = StandardUpdater(it, step, state, comm)
    trainer = Trainer(updater, stop_trigger=(args.epoch, "epoch"),
                      out=args.out)

    if comm.is_master:
        trainer.extend(LogReport(os.path.join(args.out, "cifar.jsonl")),
                       trigger=(1, "epoch"))
        trainer.extend(PrintReport(
            ["epoch", "iteration", "main/loss", "main/accuracy",
             "elapsed_time"]), trigger=(1, "epoch"))

    trainer.run()
    # preempted runs have no final observation — and must not crash
    # here, or exit 143 never reaches the supervisor
    if comm.is_master and not trainer.preempted:
        print(f"final: loss={trainer.observation['main/loss']:.4f} "
              f"acc={trainer.observation['main/accuracy']:.4f}")
    return trainer


if __name__ == "__main__":
    # supervisor exit-status contract (docs/fault_tolerance.md):
    # 0 clean, 143 preempted-and-checkpointed, 75 watchdog abort
    from chainermn_tpu.resilience.supervisor import main_exit_code
    sys.exit(main_exit_code(main))
