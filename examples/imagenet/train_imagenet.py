#!/usr/bin/env python
"""Data-parallel ImageNet ResNet-50 (BASELINE config #2 — the throughput
metric).

Reference flow (SURVEY.md §3.1): per-rank process, pure_nccl communicator,
allreduce_grad in the hot loop. Here the whole iteration — fwd/bwd, gradient
all-reduce over the mesh, SGD update, BN-stat sync — is one compiled XLA
program; bfloat16 compute feeds the MXU, gradients ride a bf16 collective
(the reference's allreduce_grad_dtype=fp16 analog).

Synthetic ImageNet-shaped data by default (no network egress); point
--data-dir at real TFRecords/folders by replacing the dataset object.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import chainermn_tpu
from chainermn_tpu.datasets.toy import ArrayDataset
from chainermn_tpu.iterators import SerialIterator
from chainermn_tpu.models.resnet import ResNet50
from chainermn_tpu.models.vit import ViT
from chainermn_tpu.training import LogReport, PrintReport, StandardUpdater, Trainer
from chainermn_tpu.training.step import make_data_parallel_train_step


def synthetic_imagenet(n, image_size, n_classes=1000, seed=0):
    protos = np.random.RandomState(99).rand(
        32, image_size, image_size, 3).astype(np.float32)
    rng = np.random.RandomState(seed)
    ys = rng.randint(0, n_classes, size=n).astype(np.int32)
    xs = protos[ys % 32] + 0.25 * rng.randn(
        n, image_size, image_size, 3).astype(np.float32)
    return ArrayDataset(xs.astype(np.float32), ys)


def main():
    p = argparse.ArgumentParser(description="ChainerMN-TPU example: ImageNet")
    p.add_argument("--batchsize", "-B", type=int, default=None,
                   help="global batch (default: 64 × n_devices)")
    p.add_argument("--epoch", "-E", type=int, default=1)
    p.add_argument("--iterations", type=int, default=None,
                   help="stop after N iterations instead of epochs")
    p.add_argument("--communicator", type=str, default="pure_nccl")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--optimizer", choices=["sgd", "lars", "lamb"],
                   default="sgd",
                   help="lars/lamb: large-batch recipes (batch-32K "
                        "ResNet needs layerwise trust ratios)")
    p.add_argument("--warmup-epochs", type=float, default=0.0,
                   help="linear LR warmup epochs (then cosine decay)")
    p.add_argument("--model", choices=["resnet50", "vit"],
                   default="resnet50",
                   help="vit: patch-16 Vision Transformer (flash-attention "
                        "encoder) instead of the conv net")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--n-train", type=int, default=2048)
    p.add_argument("--data-dir", default=None, metavar="DIR",
                   help="train from a folder-of-JPEG dataset "
                        "(DIR/<class>/*.jpg, real per-access decode) "
                        "instead of in-memory synthetic arrays; see "
                        "examples/imagenet/make_jpeg_dataset.py")
    p.add_argument("--loader", action="store_true",
                   help="feed batches through the native double-buffered "
                        "prefetch loader from a file-backed uint8 dataset "
                        "(mmap + off-thread C++ gather + on-device decode) "
                        "instead of SerialIterator over in-memory float32")
    p.add_argument("--data-file", default=None, metavar="PREFIX",
                   help="with --loader: path prefix of an existing "
                        "<PREFIX>_x.npy (uint8, N,H,W,3) + <PREFIX>_y.npy "
                        "(int32, N) pair, mmap-opened; errors if missing. "
                        "Default: a synthetic pair written under --out")
    p.add_argument("--dtype", choices=["float32", "bfloat16"],
                   default="bfloat16")
    p.add_argument("--snapshot-every", type=int, default=0,
                   metavar="ITERS",
                   help="checkpoint every N iterations (0 = off)")
    p.add_argument("--resume", action="store_true",
                   help="resume from the newest complete snapshot")
    p.add_argument("--out", "-o", default="result")
    args = p.parse_args()

    comm = chainermn_tpu.create_communicator(
        args.communicator, allreduce_grad_dtype=jnp.bfloat16
    )
    global_batch = args.batchsize or 64 * comm.size
    if comm.is_master:
        print(f"devices: {comm.size}  global batch: {global_batch}  "
              f"dtype: {args.dtype}")

    n_proc = jax.process_count()
    if args.loader:
        # File-backed uint8 dataset, mmap-opened; the native C++ loader
        # gathers each batch's rows off-thread (double-buffered) while the
        # device runs the previous step, and the uint8→bf16 decode +
        # normalize happens ON DEVICE inside the compiled step — the host
        # only ever touches bytes. Each process slices its contiguous
        # shard of the file (shared-storage layout, reference-style).
        base = args.data_file or os.path.join(args.out, "synthetic_u8")
        xpath, ypath = base + "_x.npy", base + "_y.npy"
        if args.data_file and not (os.path.exists(xpath)
                                   and os.path.exists(ypath)):
            raise SystemExit(
                f"--data-file: {xpath} / {ypath} not found (expected an "
                "existing uint8/int32 .npy pair; omit --data-file to "
                "generate synthetic data)")
        if comm.is_master and not os.path.exists(xpath):
            os.makedirs(os.path.dirname(xpath) or ".", exist_ok=True)
            rs = np.random.RandomState(0)
            np.save(xpath, rs.randint(
                0, 256, (args.n_train, args.image_size, args.image_size, 3),
                dtype=np.uint8))
            np.save(ypath, rs.randint(
                0, 1000, size=args.n_train).astype(np.int32))
        if n_proc > 1:
            comm.bcast_obj(None)  # barrier: wait for the master's write
        xs_mm = np.load(xpath, mmap_mode="r")
        ys_mm = np.load(ypath, mmap_mode="r")
        shard = len(xs_mm) // n_proc
        lo = jax.process_index() * shard
        train_len = shard * n_proc
        train = (xs_mm[lo:lo + shard], ys_mm[lo:lo + shard])
    elif args.data_dir:
        # standard folder-of-JPEG layout (root/<class>/*.jpg), decoded
        # per access — the reference example's real-ImageNet input path
        # (upstream examples/imagenet/train_imagenet.py reads a labeled
        # image list the same way). Generate a local dataset with
        # examples/imagenet/make_jpeg_dataset.py.
        from chainermn_tpu.datasets import ImageFolderDataset

        # root-only build (scatter_dataset ships the samples over the
        # object plane, so workers need no access to the root's storage —
        # same contract train_seq2seq.py relies on)
        if comm.inter_rank == 0:
            train = ImageFolderDataset(args.data_dir,
                                       image_size=args.image_size,
                                       train=True)
            n_classes = len(train.classes)
        else:
            train, n_classes = None, None
        n_classes = comm.bcast_obj(n_classes)
        train = chainermn_tpu.scatter_dataset(train, comm, shuffle=True,
                                              seed=0, shared_storage=False)
        train_len = len(train) * n_proc
    else:
        train = synthetic_imagenet(args.n_train, args.image_size)
        train = chainermn_tpu.scatter_dataset(train, comm, shuffle=True,
                                              seed=0)
        train_len = len(train) * n_proc

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    num_classes = n_classes if args.data_dir else 1000
    if args.model == "vit":
        model = ViT(num_classes=num_classes, dtype=dtype)
        mutable = None
    else:
        model = ResNet50(num_classes=num_classes, dtype=dtype)
        mutable = ("batch_stats",)
    variables = model.init(
        jax.random.PRNGKey(0),
        np.zeros((2, args.image_size, args.image_size, 3), np.float32),
    )
    params = comm.bcast_data(variables["params"])
    batch_stats = (comm.bcast_data(variables["batch_stats"])
                   if mutable else None)

    steps_per_epoch = max(1, train_len // global_batch)
    if args.warmup_epochs > 0:
        total = steps_per_epoch * args.epoch
        lr = optax.warmup_cosine_decay_schedule(
            0.0, args.lr, int(steps_per_epoch * args.warmup_epochs),
            max(total, 1))
    else:
        lr = args.lr
    base_opt = {
        "sgd": lambda: optax.sgd(lr, momentum=0.9, nesterov=True),
        # layerwise trust ratios — the large-batch ImageNet recipes
        "lars": lambda: optax.lars(lr, weight_decay=1e-4, momentum=0.9),
        "lamb": lambda: optax.lamb(lr, weight_decay=1e-4),
    }[args.optimizer]()
    optimizer = chainermn_tpu.create_multi_node_optimizer(base_opt, comm)
    state = ((params, optimizer.init(params), {"batch_stats": batch_stats})
             if mutable else (params, optimizer.init(params)))

    loss_fn = None
    if args.loader:
        from chainermn_tpu.training.step import classifier_loss

        def loss_fn(model, params, x, y, **kw):
            # on-device decode: the loader ships raw uint8 rows
            x = x.astype(dtype) / jnp.asarray(255.0, dtype)
            return classifier_loss(model, params, x, y, **kw)

    step = make_data_parallel_train_step(
        model, optimizer, comm, mutable=mutable, loss_fn=loss_fn
    )

    if args.loader:
        from chainermn_tpu.training.loader import PrefetchingLoader

        xs_shard, ys_shard = train
        it = PrefetchingLoader(xs_shard, ys_shard,
                               global_batch // n_proc,
                               shuffle=True, seed=0)
        updater = StandardUpdater(it, step, state, comm,
                                  converter=lambda b: b)
    else:
        # multi-process: each process's iterator feeds its LOCAL rows
        # (scatter_dataset already split by process); StandardUpdater
        # assembles the global batch across processes
        it = SerialIterator(train, global_batch // n_proc, shuffle=True,
                            seed=0)
        updater = StandardUpdater(it, step, state, comm)

    checkpointer = None
    restored = None
    if args.snapshot_every or args.resume:
        checkpointer = chainermn_tpu.create_multi_node_checkpointer(
            "imagenet", comm, path=args.out, async_write=True)
    if args.resume:
        restored = checkpointer.resume(updater)
        if comm.is_master and restored is not None:
            print(f"resumed from iteration {restored}")
    stop = ((args.iterations, "iteration") if args.iterations
            else (args.epoch, "epoch"))
    trainer = Trainer(updater, stop_trigger=stop, out=args.out)

    if checkpointer is not None and args.snapshot_every:
        trainer.extend(checkpointer, trigger=(args.snapshot_every,
                                              "iteration"))
    if comm.is_master:
        trainer.extend(LogReport(os.path.join(args.out, "imagenet.jsonl")),
                       trigger=(10, "iteration"))
        trainer.extend(PrintReport(
            ["epoch", "iteration", "main/loss", "main/accuracy",
             "elapsed_time"]), trigger=(10, "iteration"))

    trainer.run()
    # preempted runs have no final observation — and must not crash
    # here, or exit 143 never reaches the supervisor
    if comm.is_master and not trainer.preempted:
        obs = trainer.observation
        # count only THIS run's iterations — the counter includes the
        # restored ones after --resume
        done = obs["iteration"] - (restored or 0)
        ips = done * global_batch / obs["elapsed_time"]
        print(f"throughput: {ips:.1f} images/sec "
              f"({ips / comm.size:.1f} /chip)")
    return trainer


if __name__ == "__main__":
    # supervisor exit-status contract (docs/fault_tolerance.md):
    # 0 clean, 143 preempted-and-checkpointed, 75 watchdog abort
    from chainermn_tpu.resilience.supervisor import main_exit_code
    sys.exit(main_exit_code(main))
