#!/usr/bin/env python
"""schedtune CLI: search the collective-schedule knob space, print the
chosen schedule + its predicted DL201 overlap fraction, and write the
winner into the per-topology profile DB.

The search (chainermn_tpu/tuning/, docs/tuning.md) sweeps bucket_bytes,
bucket emission order, double-buffering (only with --allow-stale) and
reducer strategy, scoring each candidate's scheduled HLO with the real
dlint DL201/DL203 passes plus the multi-tier Topology cost model. Two
schedule sources:

* default: the canned scheduled-HLO emulator — deterministic, runs
  anywhere, no compiler needed;
* ``--aot``: AOT-compile the actual data-parallel train step per
  candidate against a described TPU topology (needs the TPU compiler
  plugin; no chips — same machinery as tools/check_overlap_schedule.py).
  Prints a skip JSON when the plugin is absent.

Usage:
  python tools/schedtune.py [--grad-bytes N] [--db PATH] [--model-key K]
                            [--intra N] [--inter N] [--lossy]
                            [--allow-stale] [--aot [v5e:2x4]] [--no-write]

Prints one JSON line: the chosen plan, the untuned-default score row,
and the full candidate table. Exit 0 always (a tuner that found no
improvement still found the answer). A run whose winner strictly beats
the default's overlap fraction sets ``"improves_overlap": true`` — the
acceptance bar for recording the plan.
"""

import json
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

#: ResNet-50 bf16 grads ≈ 51 MiB — 13 buckets at the 4 MiB default, a
#: representative payload for the canned search
DEFAULT_GRAD_BYTES = 51 << 20


def _flag(argv, name, default=None, has_value=True):
    for a in list(argv):
        if a == name and not has_value:
            argv.remove(a)
            return True
        if a == name and has_value:
            i = argv.index(a)
            argv.pop(i)
            return argv.pop(i)
        if has_value and a.startswith(name + "="):
            argv.remove(a)
            return a.split("=", 1)[1]
    return default


def _aot_compile_fn(topology_name):
    """Per-candidate AOT compilation of the real DP train step against a
    described TPU topology; returns (compile_fn, topology, total_bytes)
    or None when the compiler plugin is missing."""
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    import numpy as np

    import jax
    import jax.numpy as jnp

    try:
        from jax.experimental import topologies

        tdesc = topologies.get_topology_desc(platform="tpu",
                                             topology_name=topology_name)
    except Exception:
        return None

    import optax
    from flax import linen as nn
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import chainermn_tpu
    from chainermn_tpu.comm.xla import XlaCommunicator
    from chainermn_tpu.training.step import make_data_parallel_train_step
    from chainermn_tpu.tuning import Topology

    class Big(nn.Module):
        # same ~35M-param model as tools/check_overlap_schedule.py:
        # large enough that the all-reduce combiner keeps >1 collective
        @nn.compact
        def __call__(self, x):
            x = x.reshape((x.shape[0], -1))
            for _ in range(3):
                x = nn.relu(nn.Dense(4096)(x))
            return nn.Dense(10)(x)

    devs = np.asarray(tdesc.devices)
    mesh = Mesh(devs.reshape(2, devs.size // 2), ("dcn", "ici"))
    model = Big()
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 28, 28), jnp.float32))["params"])
    total_bytes = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(params))
    dsh = NamedSharding(mesh, P(("dcn", "ici")))
    rep = NamedSharding(mesh, P())
    x = jax.ShapeDtypeStruct((64, 28, 28), jnp.float32, sharding=dsh)
    y = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=dsh)
    opts = {
        "xla_tpu_enable_latency_hiding_scheduler": "true",
        "xla_enable_async_all_reduce": "true",
    }

    def compile_fn(cand):
        comm = XlaCommunicator(mesh=mesh,
                               dcn_bucket_bytes=cand.bucket_bytes)
        opt = optax.sgd(0.1)
        from chainermn_tpu.collectives import make_grad_reducer

        extra = {}
        if getattr(cand, "program", None) is not None:
            extra["program"] = cand.program  # 'synth' candidates
        reducer = make_grad_reducer(
            cand.strategy, comm, bucket_bytes=cand.bucket_bytes,
            bucket_order=cand.bucket_order,
            wire_format=(cand.wire_format
                         if cand.wire_format != "f32" else None),
            **extra)
        mnopt = chainermn_tpu.create_multi_node_optimizer(
            opt, comm, grad_reducer=reducer,
            double_buffering=cand.double_buffering)
        state = (params, jax.eval_shape(opt.init, params))
        if cand.double_buffering:
            state = (params, jax.eval_shape(mnopt.init, params))
        step = make_data_parallel_train_step(model, mnopt, comm,
                                             donate=False)
        astate = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=rep),
            state)
        return jax.jit(lambda s, a, b: step(s, a, b)).lower(
            astate, x, y).compile(opts).as_text()

    return compile_fn, Topology.from_comm(XlaCommunicator(mesh=mesh)), \
        total_bytes


def main():
    from chainermn_tpu.utils import use_compile_cache

    use_compile_cache()
    argv = sys.argv[1:]
    grad_bytes = int(_flag(argv, "--grad-bytes", DEFAULT_GRAD_BYTES))
    db_path = _flag(argv, "--db")
    model_key = _flag(argv, "--model-key", "default")
    intra = _flag(argv, "--intra")
    inter = _flag(argv, "--inter")
    lossy = bool(_flag(argv, "--lossy", False, has_value=False))
    allow_stale = bool(_flag(argv, "--allow-stale", False,
                             has_value=False))
    no_write = bool(_flag(argv, "--no-write", False, has_value=False))
    aot = None
    for a in list(argv):  # --aot is optionally valued: --aot[=NAME]
        if a == "--aot":
            argv.remove(a)
            aot = "v5e:2x4"
        elif a.startswith("--aot="):
            argv.remove(a)
            aot = a.split("=", 1)[1]
    if argv:
        raise SystemExit(f"unknown arguments: {argv} (see module doc)")

    from chainermn_tpu.tuning import (ProfileDB, tune, tune_canned,
                                      two_tier)

    source = "canned"
    if aot:
        built = _aot_compile_fn(aot)
        if built is None:
            print(json.dumps({
                "ok": None,
                "skip": f"no TPU compiler plugin for --aot {aot}"}))
            return
        compile_fn, topology, total_bytes = built
        result = tune(topology, total_bytes, compile_fn, lossy=lossy,
                      allow_stale=allow_stale, model_key=model_key,
                      source="aot")
        source = "aot"
        grad_bytes = total_bytes
    else:
        if intra or inter:
            topology = two_tier(int(intra or 8), int(inter or 1))
        else:
            # describe the local communicator's mesh (CPU or TPU)
            import chainermn_tpu
            from chainermn_tpu.tuning import Topology

            comm = chainermn_tpu.create_communicator("xla")
            topology = Topology.from_comm(comm)
        db_probe = ProfileDB(db_path)
        measured = db_probe.measured_for(topology) or None
        result = tune_canned(topology, grad_bytes, lossy=lossy,
                             allow_stale=allow_stale, model_key=model_key,
                             measured=measured)

    plan = result.plan
    db = ProfileDB(db_path)
    written = None
    if not no_write:
        db.put_plan(plan)
        written = db.save()

    k = max(1, math.ceil(grad_bytes / plan.bucket_bytes))
    print(f"chosen schedule  : {plan.strategy} bucket_bytes="
          f"{plan.bucket_bytes:,} ({k} buckets) order={plan.bucket_order}"
          f"{' wire=' + plan.wire_format if plan.wire_format != 'f32' else ''}"
          f"{' +double_buffering' if plan.double_buffering else ''}",
          file=sys.stderr)
    print(f"overlap fraction : {plan.overlap_fraction:.4f} (default "
          f"flat: {result.default['overlap_fraction']:.4f})",
          file=sys.stderr)
    print(json.dumps({
        "ok": True,
        "source": source,
        "topology": plan.fingerprint,
        "grad_bytes": grad_bytes,
        "chosen": plan.to_dict(),
        "default": result.default,
        "improves_overlap": result.improves_overlap,
        "n_candidates": len(result.rows),
        "candidates": result.rows,
        "db": written,
    }))


if __name__ == "__main__":
    main()
