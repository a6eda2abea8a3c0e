#!/usr/bin/env python
"""Data-parallel MNIST MLP — the bring-up example.

Mirrors the reference's examples/mnist/train_mnist.py flow (SURVEY.md §3.1):
create communicator → scatter dataset → multi-node optimizer → trainer with
rank-0 reporting — but runs as ONE process driving the whole mesh instead of
mpiexec-per-GPU, with the gradient all-reduce compiled into the step.

Run (virtual 8-device CPU mesh):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/mnist/train_mnist.py --epoch 2
On the real TPU: python examples/mnist/train_mnist.py
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import numpy as np
import optax

import chainermn_tpu
from chainermn_tpu.datasets.standard_formats import load_mnist
from chainermn_tpu.iterators import SerialIterator
from chainermn_tpu.models import MLP
from chainermn_tpu.training import (
    LogReport,
    PrintReport,
    StandardUpdater,
    Trainer,
)
from chainermn_tpu.training.evaluator import Evaluator
from chainermn_tpu.training.step import make_data_parallel_train_step, make_eval_step


def main():
    p = argparse.ArgumentParser(description="ChainerMN-TPU example: MNIST")
    p.add_argument("--batchsize", "-b", type=int, default=256,
                   help="global batch size (split over devices)")
    p.add_argument("--epoch", "-e", type=int, default=3)
    p.add_argument("--unit", "-u", type=int, default=1000)
    p.add_argument("--communicator", type=str, default="xla")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--n-train", type=int, default=4096)
    p.add_argument("--data-dir", default=None, metavar="DIR",
                   help="MNIST-layout directory (train-images-idx3-ubyte "
                        "etc., plain or .gz). Default: generate a local "
                        "IDX dataset under --out and parse THAT — the "
                        "executed input path is always the real-format "
                        "parser (reference: chainer.datasets.get_mnist)")
    p.add_argument("--grad-reducer", default="flat",
                   choices=["flat", "hierarchical", "quantized", "auto"],
                   help="gradient-reduction strategy (collectives/ "
                        "registry; 'flat' is bit-identical to the "
                        "legacy psum path)")
    p.add_argument("--wire-format", default=None,
                   choices=["f32", "bf16", "int8", "int8-block",
                            "int4-block"],
                   help="quantized wire format for compressing "
                        "reducers (docs/collectives.md"
                        "#quantized-wire-formats)")
    p.add_argument("--out", "-o", default="result")
    args = p.parse_args()

    comm = chainermn_tpu.create_communicator(args.communicator)
    if comm.is_master:
        print(f"devices: {comm.size}  mesh axes: {comm.axis_names}")

    # real-format input path: parse IDX files (the reference's MNIST
    # layout) from --data-dir, generating them locally first when no
    # directory was given. Root-only build; samples ship over the
    # object plane.
    if comm.inter_rank == 0:
        data_dir = args.data_dir
        if data_dir is None:
            data_dir = os.path.join(args.out, "mnist-data")
            if not os.path.exists(
                    os.path.join(data_dir, "train-images-idx3-ubyte")):
                from make_mnist_dataset import synth_uint8
                from chainermn_tpu.datasets.standard_formats import (
                    save_mnist)

                xs, ys = synth_uint8(args.n_train, seed=0)
                save_mnist(data_dir, xs, ys, train=True)
                xs, ys = synth_uint8(1024, seed=1)
                save_mnist(data_dir, xs, ys, train=False)
        train = load_mnist(data_dir, train=True)
        test = load_mnist(data_dir, train=False)
    else:
        train, test = None, None
    train = chainermn_tpu.scatter_dataset(train, comm, shuffle=True, seed=0,
                                          shared_storage=False)
    test = comm.bcast_obj(test)

    model = MLP(n_units=args.unit, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 28, 28), np.float32))["params"]
    params = comm.bcast_data(params)

    wf = None if args.wire_format in (None, "f32") else args.wire_format
    reducer = chainermn_tpu.make_grad_reducer(args.grad_reducer, comm,
                                              wire_format=wf)
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(args.lr), comm, grad_reducer=reducer
    )
    opt_state = jax.tree_util.tree_map(
        lambda x: x, optimizer.init(params)
    )

    step = make_data_parallel_train_step(model, optimizer, comm)
    eval_step = make_eval_step(model, comm)

    train_it = SerialIterator(train, args.batchsize, shuffle=True, seed=0)
    updater = StandardUpdater(train_it, step, (params, opt_state), comm)
    trainer = Trainer(updater, stop_trigger=(args.epoch, "epoch"),
                      out=args.out)

    evaluator = Evaluator(
        lambda: SerialIterator(test, args.batchsize, repeat=False,
                               shuffle=False),
        eval_step, updater,
    )
    evaluator = chainermn_tpu.create_multi_node_evaluator(evaluator, comm)
    trainer.extend(lambda t: evaluator(t), trigger=(1, "epoch"))

    if comm.is_master:  # reference convention: reporting on rank 0 only
        from chainermn_tpu.training.reports import ReductionReport

        trainer.extend(ReductionReport(reducer, params),
                       trigger=(1, "epoch"))
        trainer.extend(LogReport(os.path.join(args.out, "log.jsonl")),
                       trigger=(1, "epoch"))
        trainer.extend(PrintReport(
            ["epoch", "iteration", "main/loss", "main/accuracy",
             "validation/main/loss", "validation/main/accuracy",
             "elapsed_time"]), trigger=(1, "epoch"))

    trainer.run()
    # preempted runs have no final observation — and must not crash
    # here, or exit 143 never reaches the supervisor
    if comm.is_master and not trainer.preempted:
        final = trainer.observation
        print(f"final: loss={final.get('main/loss'):.4f} "
              f"val_acc={final.get('validation/main/accuracy'):.4f}")
    return trainer


if __name__ == "__main__":
    # supervisor exit-status contract (docs/fault_tolerance.md):
    # 0 clean, 143 preempted-and-checkpointed, 75 watchdog abort
    from chainermn_tpu.resilience.supervisor import main_exit_code
    sys.exit(main_exit_code(main))
