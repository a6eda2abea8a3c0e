#!/usr/bin/env python
"""Perf sweep for the ResNet-50 bench: batch size, scan-amortized dispatch,
space-to-depth stem, gradient-reduction strategy. Prints one JSON line
per variant.

Usage: python tools/bench_sweep.py BATCH N_SCAN S2D
                                   [--grad-reducer=flat,hierarchical,...]
                                   [--wire-format=f32,bf16,int8-block,...]
                                   [--tune[=DB_PATH]]
  --grad-reducer sweeps collectives/ strategies; each line carries the
  strategy's per-step payload and wire bytes from the reducer's bucket
  plan. Off TPU the throughput deltas are an honest null (BASELINE.md);
  the byte accounting is exact everywhere.
  --wire-format sweeps the quantized wire formats
  (docs/collectives.md#quantized-wire-formats; narrow formats default
  the strategy to 'quantized'); each line carries exact wire bytes and
  the wire/payload compression ratio.
  --tune builds the optimizer from the schedtune profile DB
  (docs/tuning.md; run tools/schedtune.py first) and adds the plan's
  tuning/overlap_frac + tuning/bucket_bytes keys to the JSON line."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np


def run_variant(batch, n_scan, s2d, n_iters=10, grad_reducer=None,
                tune=None, wire_format=None):
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import chainermn_tpu
    from chainermn_tpu.models.resnet import ResNet50
    from chainermn_tpu.training.step import make_data_parallel_train_step

    comm = chainermn_tpu.create_communicator("xla")
    n_dev = comm.size
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                     space_to_depth=s2d)
    image = np.zeros((2, 224, 224, 3), np.float32)
    mutable = ("batch_stats",)

    global_batch = batch * n_dev
    variables = model.init(jax.random.PRNGKey(0), image)
    params = comm.bcast_data(variables["params"])
    extra = {k: comm.bcast_data(variables[k]) for k in mutable}
    reducer = None
    wf = None if wire_format in (None, "f32") else wire_format
    if grad_reducer or wf:
        from chainermn_tpu.collectives import make_grad_reducer

        # a narrow wire with no explicit strategy means 'quantized'
        reducer = make_grad_reducer(grad_reducer or "quantized", comm,
                                    wire_format=wf)
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm, grad_reducer=reducer,
        tune=tune)
    plan = getattr(opt, "plan", None)
    if plan is not None and reducer is None:
        reducer = opt.grad_reducer  # the plan-built reducer
    state = (params, opt.init(params), extra)
    step = make_data_parallel_train_step(model, opt, comm, mutable=mutable)

    x = np.random.RandomState(0).rand(
        global_batch, 224, 224, 3).astype(np.float32)
    y = np.random.RandomState(1).randint(
        0, 1000, size=(global_batch,)).astype(np.int32)
    dsh = NamedSharding(comm.mesh, P(comm.axis_names[0]))
    x = jax.device_put(x, dsh)
    y = jax.device_put(y, dsh)

    if n_scan > 1:
        base = step

        def multi(state, x, y):
            def body(s, _):
                s, m = base(s, x, y)
                return s, m
            return lax.scan(body, state, None, length=n_scan)
        multi = jax.jit(multi, donate_argnums=(0,))
        state, m = multi(state, x, y)  # warmup: the compile
        float(jax.tree_util.tree_leaves(m)[0][-1])
        t0 = time.perf_counter()
        reps = max(1, n_iters // n_scan)
        for _ in range(reps):
            state, m = multi(state, x, y)
        float(jax.tree_util.tree_leaves(m)[0][-1])
        dt = time.perf_counter() - t0
        total = reps * n_scan * global_batch
    else:
        state, m = step(state, x, y)  # warmup: the compile
        float(m["main/loss"])
        t0 = time.perf_counter()
        for _ in range(n_iters):
            # timed region: sync once at the end — device-throughput
            # methodology, same as bench_lm.py
            state, m = step(state, x, y)  # dlint: disable=DL104
        float(m["main/loss"])
        dt = time.perf_counter() - t0
        total = n_iters * global_batch

    per_chip = total / dt / n_dev
    line = {
        "batch": batch, "scan": n_scan, "s2d": s2d,
        "images_per_sec_per_chip": round(per_chip, 1),
    }
    if reducer is not None:
        rows = reducer.plan(params)
        payload = sum(r["bytes"] for r in rows)
        wire = sum(r["wire_bytes"] for r in rows)
        line["grad_reducer"] = reducer.name
        line["comm_bytes_per_step"] = payload
        line["comm_wire_bytes_per_step"] = wire
        line["comm_wire_compression"] = round(
            wire / payload, 6) if payload else 1.0
    if wire_format is not None:
        line["wire_format"] = wire_format
    if plan is not None:
        line["tuning/overlap_frac"] = plan.overlap_fraction
        line["tuning/bucket_bytes"] = plan.bucket_bytes
        line["tuning/strategy"] = plan.strategy
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    from chainermn_tpu.utils import use_compile_cache

    use_compile_cache()
    argv = sys.argv[1:]
    reducers = [None]
    for a in list(argv):
        if a.startswith("--grad-reducer"):
            reducers = a.split("=", 1)[1].split(",")
            argv.remove(a)
    wire_formats = [None]
    for a in list(argv):
        if a.startswith("--wire-format"):
            wire_formats = a.split("=", 1)[1].split(",")
            argv.remove(a)
    tune = None
    for a in list(argv):
        if a.startswith("--tune"):
            tune = a.split("=", 1)[1] if "=" in a else True
            argv.remove(a)
    batch = int(argv[0])
    n_scan = int(argv[1])
    s2d = argv[2] == "1"
    for gr in reducers:
        for wfmt in wire_formats:
            run_variant(batch, n_scan, s2d, grad_reducer=gr, tune=tune,
                        wire_format=wfmt)
