#!/usr/bin/env python
"""Model-parallel MNIST with EAGER differentiable send/recv — the
reference's define-by-run pattern as real processes.

Reference shape (upstream model-parallel MNIST examples): rank 0 runs the
first half of the model and ``functions.send``s the activation mid-
forward; rank 1 ``recv``s, finishes the model, computes the loss, and
``loss.backward()`` transports the gradient back — blocking MPI P2P under
define-by-run autograd. Here the same per-process script runs under
``jax.grad`` with :mod:`chainermn_tpu.functions.eager_p2p` (custom_vjp
over ordered io_callbacks on the object plane). Note the two documented
deviations: ``eager_recv`` declares the incoming aval, and is
``anchor=``-ed to the receiving side's parameters so the reverse
transport provably runs (MIGRATION.md).

Run (spawns 2 local processes automatically):

    python examples/model_parallel/train_mnist_eager_p2p.py --steps 30

or launch the two workers yourself, mpiexec-style:

    python ... --proc-id 0 --port 12345 &
    python ... --proc-id 1 --port 12345
"""

import argparse
import os
import socket
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batchsize", "-b", type=int, default=128)
    p.add_argument("--unit", type=int, default=128)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--proc-id", type=int, default=None,
                   help="worker mode (internal); omit to auto-spawn both")
    p.add_argument("--port", type=int, default=None)
    return p.parse_args()


def spawn_workers(args):
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    # both workers on the CPU backend: the transport is host-level (the
    # object plane), and two processes sharing one local TPU chip would
    # deadlock. On a real multi-host pod each process owns its devices —
    # export CHAINERMN_EAGER_EXAMPLE_PLATFORM to override.
    platform = os.environ.get("CHAINERMN_EAGER_EXAMPLE_PLATFORM", "cpu")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = platform
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--steps", str(args.steps), "-b", str(args.batchsize),
             "--unit", str(args.unit), "--lr", str(args.lr),
             "--proc-id", str(i), "--port", str(port)],
            env=env)
        for i in range(2)
    ]
    rc = [p.wait() for p in procs]
    if any(rc):
        raise SystemExit(f"workers exited {rc}")


def worker(args):
    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{args.port}", num_processes=2,
        process_id=args.proc_id)

    import jax.numpy as jnp
    import numpy as np

    import chainermn_tpu
    from chainermn_tpu.datasets.toy import synthetic_mnist
    from chainermn_tpu.functions import eager_recv, eager_send

    comm = chainermn_tpu.create_communicator("xla")
    me = comm.rank
    rs = np.random.RandomState(0)
    ds = synthetic_mnist(args.batchsize * 8, seed=0)
    u = args.unit

    if me == 0:
        # first half: flatten → hidden. Returns the dangling delegate
        # token tied into the "loss" so backward visits the send.
        w0 = jnp.asarray(rs.randn(784, u) * 0.05, jnp.float32)

        def half0(w, x):
            hid = jnp.tanh(x.reshape(len(x), -1) @ w)
            return eager_send(hid, comm, rank=1)

        w = w0
        rs_idx = np.random.RandomState(7)  # same stream on both ranks
        for step in range(args.steps):
            idx = rs_idx.randint(0, len(ds), args.batchsize)
            x = jnp.asarray(np.stack([ds[i][0] for i in idx]))
            _, dw = jax.value_and_grad(half0)(w, x)
            w = w - args.lr * dw
        print("rank 0 done (first half trained via transported grads)",
              flush=True)
    else:
        # second half: hidden → logits → CE loss. The recv is anchored
        # to THIS side's params so its vjp (the gradient send-back) runs.
        w1 = jnp.asarray(rs.randn(u, 10) * 0.05, jnp.float32)

        def half1(w, y):
            hid = eager_recv(comm, rank=0,
                             shape=(args.batchsize, u),
                             dtype=jnp.float32, anchor=w)
            logits = hid @ w
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(
                logits, y[:, None], axis=-1)[:, 0]
            return jnp.mean(lse - picked)

        rs_idx = np.random.RandomState(7)  # same stream as rank 0
        w = w1
        for step in range(args.steps):
            idx = rs_idx.randint(0, len(ds), args.batchsize)
            y = jnp.asarray(np.stack([ds[i][1] for i in idx]))
            loss, dw = jax.value_and_grad(half1)(w, y)
            w = w - args.lr * dw
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step}: loss {float(loss):.4f}", flush=True)
        final = float(loss)
        assert final < 2.0, f"did not learn: {final}"
        print("rank 1 done", flush=True)


def main():
    args = parse_args()
    if args.proc_id is None:
        spawn_workers(args)
    else:
        worker(args)


if __name__ == "__main__":
    # supervisor exit-status contract (docs/fault_tolerance.md):
    # 0 clean, 143 preempted-and-checkpointed, 75 watchdog abort
    from chainermn_tpu.resilience.supervisor import main_exit_code
    sys.exit(main_exit_code(main))
