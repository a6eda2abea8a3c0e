#!/usr/bin/env python
"""Transformer-LM training throughput (tokens/sec) on the available chips.

Secondary benchmark (the driver's recorded metric is bench.py's ResNet-50,
which also folds this number into its JSON line as the LM regression
gate): a GPT-small-ish causal LM on the flash-attention path, bf16
compute, data-parallel step factory. Prints one JSON line per config.

Usage: python tools/bench_lm.py [d_model n_layers seq_len batch
                                 [loss [d_head [qkv_layout]]]]
                                [--autotune-blocks] [--tune[=DB_PATH]]
                                [--grad-reducer=flat,hierarchical,...]
                                [--wire-format=f32,bf16,int8,int8-block,int4-block]
  --tune: build the optimizer from the schedtune profile DB
  (create_multi_node_optimizer(tune=...), docs/tuning.md; default DB
  path unless =DB_PATH given — run tools/schedtune.py first). The JSON
  line gains the chosen plan's ``tuning/overlap_frac``,
  ``tuning/bucket_bytes``, and ``tuning/strategy``; off TPU the
  throughput delta of the tuned plan is the same honest null as below.
  --grad-reducer: comma-separated gradient-reduction strategies
  (collectives/ registry: flat | hierarchical | quantized | auto); one
  JSON line per strategy, with the strategy's per-step payload and wire
  bytes from the reducer's bucket plan. Off TPU the throughput deltas
  are meaningless (host-platform collectives are memcpys — BASELINE.md
  records the honest null); the byte accounting is exact everywhere.
  --wire-format: comma-separated wire formats
  (docs/collectives.md#quantized-wire-formats); one JSON line per
  format. 'f32' runs the flat reference; the narrow formats default the
  strategy to 'quantized' when --grad-reducer is absent. Each line's
  ``comm_wire_bytes_per_step`` is EXACT (scale sidecars included) and
  ``comm_wire_compression`` is wire/payload — byte accounting is
  host-side and correct off-TPU, like --grad-reducer.
  --autotune-blocks: time the flash-attention (block_q, block_k)
  candidates for this shape (ops/autotune.py) and build the model with
  the winner; off-TPU the tuner returns the defaults untimed (recorded
  as an honest null in BASELINE.md)
  loss: 'unfused' (default) or 'fused' — the fused head+CE Pallas kernel
  (ops/fused_ce.py; measured throughput-neutral, −2 GB logits memory)
  d_head: head dim (default 64; 128 halves the QK^T MXU inefficiency the
  roofline attributes to d=64 — docs/lm_roofline.md: +26% measured)
  qkv_layout: 'blhd' (default) or 'bhld' — head-major pivot-free
  attention tensors (+3% measured; BASELINE.md r4)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np


def measure(d_model=768, n_layers=12, seq_len=2048, batch=8,
            loss_kind="unfused", d_head=64, scan_k=4, n_iters=6,
            qkv_layout="blhd", autotune_blocks=False, grad_reducer=None,
            tune=None, wire_format=None):
    """Measure LM training throughput; returns (tokens_per_sec_per_chip,
    config dict). Importable — bench.py reuses this as its LM gate."""
    import jax
    import jax.numpy as jnp
    import optax

    import chainermn_tpu
    from chainermn_tpu.models.transformer import (TransformerLM,
                                                  lm_loss_with_aux)
    from chainermn_tpu.training.step import make_data_parallel_train_step

    if loss_kind not in ("unfused", "fused"):
        raise ValueError(f"loss must be 'unfused' or 'fused', got "
                         f"{loss_kind!r}")
    if d_model % d_head:
        raise ValueError(f"d_head {d_head} must divide d_model {d_model}")

    comm = chainermn_tpu.create_communicator("xla")
    blocks = None
    if autotune_blocks:
        from chainermn_tpu.ops.autotune import tune_flash_blocks

        blocks = tune_flash_blocks(batch, seq_len, d_model // d_head,
                                   d_head, dtype=jnp.bfloat16)
    model = TransformerLM(
        vocab=32768, d_model=d_model, n_heads=d_model // d_head,
        n_layers=n_layers, d_ff=4 * d_model, max_len=seq_len,
        pos_emb="rope", attention="flash", dtype=jnp.bfloat16,
        qkv_layout=qkv_layout, attention_blocks=blocks)

    toks = np.random.RandomState(0).randint(
        0, 32768, size=(batch * comm.size, seq_len + 1)).astype(np.int32)
    params = comm.bcast_data(
        model.init(jax.random.PRNGKey(0), toks[:1, :-1])["params"])
    reducer = None
    wf = None if wire_format in (None, "f32") else wire_format
    if grad_reducer or wf:
        from chainermn_tpu.collectives import make_grad_reducer

        # a narrow wire with no explicit strategy means 'quantized'
        reducer = make_grad_reducer(grad_reducer or "quantized", comm,
                                    wire_format=wf)
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.adamw(3e-4), comm, grad_reducer=reducer, tune=tune)
    plan = getattr(opt, "plan", None)
    if plan is not None and reducer is None:
        reducer = opt.grad_reducer  # the plan-built reducer
    # K steps per dispatch (same methodology as bench.py); the token
    # stack reuses ONE device batch K times — this row times the step,
    # not an input pipeline
    if loss_kind == "fused":
        from chainermn_tpu.ops import fused_lm_loss

        lf = fused_lm_loss
    else:
        lf = lm_loss_with_aux
    step = make_data_parallel_train_step(
        model, opt, comm, loss_fn=lf, scan_steps=scan_k)
    state = (params, opt.init(params))

    from jax.sharding import NamedSharding, PartitionSpec as P

    dsh = NamedSharding(comm.mesh,
                        P(None, comm.axis_names[0]))
    xs = jax.device_put(np.broadcast_to(
        toks[None, :, :-1], (scan_k,) + toks[:, :-1].shape).copy(), dsh)
    ys = jax.device_put(np.broadcast_to(
        toks[None, :, 1:], (scan_k,) + toks[:, 1:].shape).copy(), dsh)

    state, m = step(state, xs, ys)  # warmup: the compile
    float(m["main/loss"][-1])
    t0 = time.perf_counter()
    for _ in range(n_iters):
        # timed region syncs ONCE at the end on purpose: dispatches
        # queue asynchronously and the figure is device throughput
        state, m = step(state, xs, ys)  # dlint: disable=DL104
    final = float(m["main/loss"][-1])
    dt = time.perf_counter() - t0
    assert final == final, "loss is NaN"

    tokens_per_sec = n_iters * scan_k * batch * comm.size * seq_len / dt
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    config = {"d_model": d_model, "n_layers": n_layers,
              "seq_len": seq_len, "batch_per_chip": batch,
              "d_head": d_head,
              "params_m": round(n_params / 1e6, 1),
              "loss": loss_kind, "qkv_layout": qkv_layout,
              "attention_blocks": blocks}
    if reducer is not None:
        rows = reducer.plan(params)
        payload = sum(r["bytes"] for r in rows)
        wire = sum(r["wire_bytes"] for r in rows)
        config["grad_reducer"] = reducer.name
        config["comm_bytes_per_step"] = payload
        config["comm_wire_bytes_per_step"] = wire
        config["comm_wire_compression"] = round(
            wire / payload, 6) if payload else 1.0
    if wire_format is not None:
        config["wire_format"] = wire_format
    if plan is not None:
        config["tuning/overlap_frac"] = plan.overlap_fraction
        config["tuning/bucket_bytes"] = plan.bucket_bytes
        config["tuning/strategy"] = plan.strategy
        config["tuning/source"] = plan.source
    return tokens_per_sec / comm.size, config


def wire_report(wire_format="f32", d_model=768, n_layers=12,
                seq_len=2048, d_head=64):
    """Exact per-step wire accounting for the LM bench config WITHOUT
    running a step: abstract params (``jax.eval_shape`` of the model
    init — zero FLOPs, zero device memory) through the reducer's bucket
    plan. Works anywhere; bench.py's wire gate is built on this."""
    import jax
    import jax.numpy as jnp

    import chainermn_tpu
    from chainermn_tpu.collectives import make_grad_reducer
    from chainermn_tpu.models.transformer import TransformerLM

    comm = chainermn_tpu.create_communicator("xla")
    model = TransformerLM(
        vocab=32768, d_model=d_model, n_heads=d_model // d_head,
        n_layers=n_layers, d_ff=4 * d_model, max_len=seq_len,
        pos_emb="rope", attention="flash", dtype=jnp.bfloat16)
    toks = jax.ShapeDtypeStruct((1, seq_len), jnp.int32)
    params = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t)["params"], toks)
    wf = None if wire_format in (None, "f32") else wire_format
    reducer = make_grad_reducer("quantized" if wf else "flat", comm,
                                wire_format=wf)
    rows = reducer.plan(params)
    payload = sum(r["bytes"] for r in rows)
    wire = sum(r["wire_bytes"] for r in rows)
    return {"wire_format": wire_format or "f32",
            "payload_bytes": payload,
            "wire_bytes": wire,
            "compression": round(wire / payload, 6) if payload else 1.0}


def main():
    from chainermn_tpu.utils import use_compile_cache

    use_compile_cache()
    argv = sys.argv[1:]
    autotune = "--autotune-blocks" in argv
    if autotune:
        argv.remove("--autotune-blocks")
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
    d_model = int(argv[0]) if len(argv) > 0 else 768
    n_layers = int(argv[1]) if len(argv) > 1 else 12
    seq_len = int(argv[2]) if len(argv) > 2 else 2048
    batch = int(argv[3]) if len(argv) > 3 else 8
    loss_kind = argv[4] if len(argv) > 4 else "unfused"
    d_head = int(argv[5]) if len(argv) > 5 else 64
    qkv_layout = argv[6] if len(argv) > 6 else "blhd"
    for gr in reducers:
        for wfmt in wire_formats:
            try:
                per_chip, config = measure(d_model, n_layers, seq_len,
                                           batch, loss_kind, d_head,
                                           qkv_layout=qkv_layout,
                                           autotune_blocks=autotune,
                                           grad_reducer=gr, tune=tune,
                                           wire_format=wfmt)
            except ValueError as e:
                raise SystemExit(str(e))
            print(json.dumps({
                "metric": "transformer_lm_tokens_per_sec_per_chip",
                "value": round(per_chip, 1),
                "unit": "tokens/sec/chip",
                "config": config,
            }), flush=True)


if __name__ == "__main__":
    main()
