#!/usr/bin/env python
"""Benchmark entry point — prints ONE JSON line.

Measures data-parallel training throughput (images/sec) of ResNet-50 on
the available devices, and on a TPU the two Transformer-LM rows
(tools/bench_lm.py). A row that fails raises: the process exits
non-zero instead of printing a partial record.
``vs_baseline`` divides by 2506.43 im/s/chip, a figure from an earlier
installation whose record was deleted in PR 21 — kept only so the key
stays in the line until the benchmark is rebuilt (ROADMAP S1).

Modes:
  default       pre-staged device tensors, synthesized ON DEVICE (pure
                device throughput).
  --realistic   pays an input pipeline every step: a device-resident
                uint8 dataset (the ImageNet-shape analog of an HBM-fit
                corpus), per-step shuffled indices from the host, and a
                separate on-device gather + uint8→bf16 decode + normalize
                program ahead of the SAME compiled train step the default
                mode runs.
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

import jax
import jax.numpy as jnp
import optax

import chainermn_tpu
from chainermn_tpu.utils import on_tpu, use_compile_cache


SCAN_K = 8  # optimizer steps compiled per dispatch (both modes MUST share
#             one step program — the default-vs-realistic comparison is
#             meaningless otherwise)

# vs_baseline's denominator: the first measurement of this benchmark, on
# an earlier installation (record deleted in PR 21). No published
# reference figure exists (BASELINE.json .published == {}).
RECORDED_BASELINE_IMG_PER_SEC = 2506.43


def _init_state_and_step(comm, model, image, mutable):
    """Model/optimizer state + the ONE train-step program both modes run.

    K=SCAN_K steps per dispatch (lax.scan inside the compiled program),
    so the host's per-dispatch cost is paid once per K steps.
    """
    from chainermn_tpu.training.step import make_data_parallel_train_step

    variables = model.init(jax.random.PRNGKey(0), image)
    params = comm.bcast_data(variables["params"])
    extra = (
        {k: comm.bcast_data(variables[k]) for k in mutable}
        if mutable else None
    )
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm
    )
    state = (
        (params, opt.init(params), extra)
        if mutable else (params, opt.init(params))
    )
    step = make_data_parallel_train_step(model, opt, comm, mutable=mutable,
                                         scan_steps=SCAN_K)
    return state, step


def _timed_images_per_sec(one_iter, state, global_batch, n_iters=4):
    """One warmup dispatch (the compile), then the timed window, shared
    by both modes. float() of the last loss waits for every step before
    it: dispatch is asynchronous, so the clock stops on a value."""
    state, m = one_iter(state)
    float(m["main/loss"][-1])
    t0 = time.perf_counter()
    for _ in range(n_iters):
        state, m = one_iter(state)
    final_loss = float(m["main/loss"][-1])
    dt = time.perf_counter() - t0
    assert final_loss == final_loss, "loss is NaN"
    return n_iters * SCAN_K * global_batch / dt


def _bench_default(comm, model, image, per_device_batch, mutable):
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = comm.size
    global_batch = per_device_batch * n_dev
    state, step = _init_state_and_step(comm, model, image, mutable)
    scan_k = SCAN_K

    shape = (scan_k, global_batch) + image.shape[1:]
    axes = comm.axis_names
    dsh = NamedSharding(comm.mesh,
                        P(None, axes if len(axes) > 1 else axes[0]))
    in_dtype, n_classes = jnp.bfloat16, 1000

    @functools.partial(jax.jit, out_shardings=(dsh, dsh))
    def synth(key):
        kx, ky = jax.random.split(key)
        xs = jax.random.uniform(kx, shape, in_dtype)
        ys = jax.random.randint(ky, shape[:2], 0, n_classes, jnp.int32)
        return xs, ys

    xs, ys = synth(jax.random.PRNGKey(1))

    return _timed_images_per_sec(
        lambda st: step(st, xs, ys), state, global_batch)


def _bench_realistic(comm, model, image, per_device_batch, mutable):
    """Input-pipeline-paying variant: device-resident uint8 dataset,
    host-shuffled indices, an on-device gather+decode program, then the
    EXACT train-step program the default mode benchmarks (two dispatches
    + one ~8 KB index transfer per K-step iteration)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = comm.mesh
    axes = comm.axis_names
    ax = axes if len(axes) > 1 else axes[0]
    global_batch = per_device_batch * comm.size
    scan_k = SCAN_K
    n_data = 2048  # device-resident corpus (uint8: 308 MB at 224px)
    in_dtype, n_classes = jnp.bfloat16, 1000

    rep = NamedSharding(mesh, P())

    @functools.partial(jax.jit, out_shardings=(rep, rep))
    def synth_data(key):
        kx, ky = jax.random.split(key)
        return (jax.random.randint(kx, (n_data,) + image.shape[1:], 0, 256,
                                   jnp.uint8),
                jax.random.randint(ky, (n_data,), 0, n_classes, jnp.int32))

    data_x, data_y = synth_data(jax.random.PRNGKey(2))
    state, step = _init_state_and_step(comm, model, image, mutable)

    dsh = NamedSharding(mesh, P(None, ax))

    @functools.partial(jax.jit, out_shardings=(dsh, dsh))
    def assemble(data_x, data_y, idxs):
        # the device side of the input pipeline: gather + decode
        xs = data_x[idxs].astype(in_dtype) / jnp.asarray(255.0, in_dtype)
        return xs, data_y[idxs]

    idx_sh = NamedSharding(mesh, P(None, ax))
    rs = np.random.RandomState(0)

    def next_idxs():
        # the host side: K fresh shuffled index batches per dispatch
        return jax.device_put(
            rs.randint(0, n_data, size=(scan_k, global_batch))
            .astype(np.int32), idx_sh)

    def one_iter(state):
        xs, ys = assemble(data_x, data_y, next_idxs())
        return step(state, xs, ys)

    return _timed_images_per_sec(one_iter, state, global_batch)


def main():
    realistic = "--realistic" in sys.argv
    use_compile_cache()

    comm = chainermn_tpu.create_communicator("xla")
    n_dev = comm.size

    from chainermn_tpu.models.resnet import ResNet50

    # bf16 compute (fp32 params/BN stats) keeps the MXU fed; the
    # space-to-depth stem + batch 256 per chip measured fastest on v5e
    # (earlier installation: 2442 im/s vs 2363 at b128/plain stem).
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                     space_to_depth=True)
    image = np.zeros((2, 224, 224, 3), np.float32)
    per_device_batch = 256
    mutable = ("batch_stats",)

    bench = _bench_realistic if realistic else _bench_default
    images_per_sec = bench(comm, model, image, per_device_batch, mutable)
    per_chip = images_per_sec / n_dev
    suffix = "_realistic" if realistic else ""
    # the recorded baseline is the default-mode number; --realistic has
    # no recorded denominator and reports 1.0
    vs = 1.0 if realistic else per_chip / RECORDED_BASELINE_IMG_PER_SEC
    record = {
        "metric": f"resnet50_train_images_per_sec_per_chip{suffix}",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(vs, 4),
    }

    # LM regression gates, folded into the SAME json line (extra keys are
    # harmless to any parser of the headline metric). TWO gated configs,
    # each floored ~3% under its round-4 measurement (earlier
    # installation) so a 5% kernel regression in either fails the gate:
    #   contract  — b=4, d_head=64, bhld, fused CE
    #   frontier  — same but d_head=128
    # TPU-only: a throughput floor means nothing for interpreted kernels.
    # A row that raises sinks the run — a gate that cannot be produced
    # is a failure, not a key in the record.
    if "--no-lm" not in sys.argv and on_tpu():
        from tools.bench_lm import measure

        gates = [
            ("lm", dict(batch=4, loss_kind="fused", qkv_layout="bhld"),
             107_000.0),
            ("lm_frontier",
             dict(batch=4, loss_kind="fused", qkv_layout="bhld",
                  d_head=128),
             130_000.0),
        ]
        ok = True
        for prefix, kw, floor in gates:
            per, cfg = measure(**kw)
            record[f"{prefix}_tokens_per_sec_per_chip"] = round(per, 1)
            record[f"{prefix}_config"] = cfg
            record[f"{prefix}_floor_tokens_per_sec"] = floor
            ok = ok and per >= floor
        record["lm_gate_ok"] = bool(ok)

    # quantized-wire byte gate (docs/collectives.md#quantized-wire-formats),
    # folded into the same JSON line. The accounting is host-side and
    # byte-exact (tools/bench_lm.py wire_report runs the LM bench config's
    # abstract params through the reducer's bucket plan — zero FLOPs), so
    # unlike the throughput gates this one is NOT TPU-gated: int8-block
    # must cut the wire to <= 0.27x of flat f32 and int4-block to
    # <= 0.14x, scale sidecars included.
    try:
        from tools.bench_lm import wire_report

        flat_wire = wire_report("f32")["wire_bytes"]
        wire_ok = bool(flat_wire)
        for wfmt, ceil in (("int8-block", 0.27), ("int4-block", 0.14)):
            rep = wire_report(wfmt)
            ratio = rep["wire_bytes"] / flat_wire if flat_wire else 1.0
            record[f"wire_{wfmt}_bytes"] = rep["wire_bytes"]
            record[f"wire_{wfmt}_vs_flat"] = round(ratio, 6)
            wire_ok = wire_ok and ratio <= ceil
        record["wire_flat_bytes"] = flat_wire
        record["wire_gate_ok"] = wire_ok
    except Exception as e:  # never sink the headline metric
        record["wire_gate_error"] = f"{type(e).__name__}: {e}"[:300]

    # schedtune tuned-vs-default overlap fraction (docs/tuning.md),
    # folded into the same JSON line. The fractions come from the canned
    # scheduled-HLO search over this model's gradient payload — honest
    # about their source (``tuning_source``); the THROUGHPUT delta of
    # applying the tuned plan stays an honest null on a CPU-mesh machine
    # (host-platform collectives are memcpys, BASELINE.md rounds 6-7).
    try:
        from chainermn_tpu.tuning import Topology, tune_canned

        g = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), image))
        try:
            g = g["params"]  # grads cover params, not batch_stats
        except (KeyError, TypeError, IndexError):
            pass
        grad_bytes = sum(
            int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
            for l in jax.tree_util.tree_leaves(g))
        tuned = tune_canned(Topology.from_comm(comm), grad_bytes)
        record["tuning_source"] = "canned"
        record["tuning_grad_bytes"] = grad_bytes
        record["tuned_overlap_frac"] = tuned.plan.overlap_fraction
        record["default_overlap_frac"] = tuned.default[
            "overlap_fraction"]
        record["tuned_bucket_bytes"] = tuned.plan.bucket_bytes
        record["tuned_strategy"] = tuned.plan.strategy
        record["tuned_throughput_delta"] = (
            None if jax.default_backend() == "cpu" else "unmeasured")
    except Exception as e:  # never sink the headline metric
        record["tuning_error"] = f"{type(e).__name__}: {e}"[:300]

    # synthesized-program gate (docs/tuning.md#from-knobs-to-programs),
    # folded into the same JSON line. On a factored two-tier view of
    # this machine the search space includes whole synthesized programs
    # (chainermn_tpu/synthesis/); the gate asserts the best program's
    # DL201 overlap fraction is >= the best FIXED reducer's on the same
    # canned fixtures — the widened space must never lose to its own
    # subset, and on the scatter-led fixtures it strictly wins. Scoring
    # is canned + cost-model (no devices), so the gate is NOT TPU-gated.
    try:
        from chainermn_tpu.tuning import tune_canned, two_tier

        sg_bytes = record.get("tuning_grad_bytes", 51 << 20)
        intra = max(1, n_dev // 2)
        synth_res = tune_canned(two_tier(intra, n_dev // intra), sg_bytes)
        synth_rows = [r for r in synth_res.rows
                      if r["candidate"]["strategy"] == "synth"]
        fixed_rows = [r for r in synth_res.rows
                      if r["candidate"]["strategy"] != "synth"]
        best_synth = max(r["overlap_fraction"] for r in synth_rows)
        best_fixed = max(r["overlap_fraction"] for r in fixed_rows)
        record["synth_n_programs"] = len(
            {r["candidate"]["program"]["name"] for r in synth_rows})
        record["synth_best_overlap_frac"] = best_synth
        record["synth_best_fixed_overlap_frac"] = best_fixed
        record["synth_winner"] = synth_res.plan.strategy
        if synth_res.plan.program is not None:
            record["synth_winner_program"] = synth_res.plan.program["name"]
        record["synth_gate_ok"] = bool(synth_rows
                                       and best_synth >= best_fixed)
    except Exception as e:  # never sink the headline metric
        record["synth_gate_error"] = f"{type(e).__name__}: {e}"[:300]

    # serving decode proof (docs/serving.md), folded into the same JSON
    # line: the paged-KV cached decode compiles ONE program where the
    # naive full-recompute loop compiles one PER TOKEN, with identical
    # greedy streams — and the multi-token decode_k program emits the
    # same stream from one trace while moving ≤ 8 device→host bytes per
    # token (on-device sampling, DL110's observable). The trace counts
    # and byte gate are structural and hold on any backend; the
    # wall-clock side stays an honest null off-TPU
    # (``serving_honest_null`` — tools/bench_serve.py reports the same).
    try:
        from tools.bench_serve import (measure_cached, measure_decode_k,
                                       measure_recompute)

        from chainermn_tpu.models.transformer import TransformerLM

        lm = TransformerLM(vocab=64, d_model=32, n_heads=4, n_layers=2,
                           d_ff=64, max_len=64, attention="reference",
                           pos_emb="rope")
        lp = lm.init(jax.random.PRNGKey(0),
                     jnp.zeros((1, 4), jnp.int32))["params"]
        prompt = (np.arange(1, 9, dtype=np.int32) % 64)[None]
        n_new = 12
        cached = measure_cached(lm, lp, prompt, n_new, capacity=64)
        recomp = measure_recompute(lm, lp, prompt, n_new)
        multi = measure_decode_k(lm, lp, prompt, n_new, capacity=64)
        record["serving_honest_null"] = jax.default_backend() != "tpu"
        record["serving_cached_traces"] = cached["traces"]
        record["serving_recompute_traces"] = recomp["traces"]
        record["serving_decode_k_traces"] = multi["traces"]
        record["serving_cached_tokens_per_s"] = cached["tokens_per_s"]
        record["serving_recompute_tokens_per_s"] = recomp["tokens_per_s"]
        record["serving_decode_k_tokens_per_s"] = multi["tokens_per_s"]
        record["serving_host_bytes_per_token"] = (
            multi["host_bytes_per_token"])
        record["serving_streams_identical"] = (
            cached["tokens"] == recomp["tokens"] == multi["tokens"])
        record["serving_gate_ok"] = bool(
            cached["tokens"] == recomp["tokens"] == multi["tokens"]
            and cached["traces"] == 1 and recomp["traces"] == n_new
            and multi["traces"] == 1
            and multi["host_bytes_per_token"] <= 8.0)
    except Exception as e:  # never sink the headline metric
        record["serving_error"] = f"{type(e).__name__}: {e}"[:300]

    # serving fleet gate (docs/serving.md#the-fleet-many-engines-one-
    # front-door), folded into the same JSON line. Three structural
    # claims that hold on any backend: (1) streams routed across a
    # 2-replica fleet are IDENTICAL to the single-engine streams
    # (placement must not perturb decode); (2) raw-f32 disaggregated
    # prefill→decode handoff streams are bitwise the single-engine
    # streams; (3) the int8-block handoff wire is <= 0.27x the raw f32
    # wire, scale sidecars and PRNG key included. Throughput stays an
    # honest null off-TPU, same as the serving section.
    try:
        from chainermn_tpu.fleet import (DisaggregatedFleet, FleetReport,
                                         Router)
        from chainermn_tpu.models.transformer import TransformerLM
        from chainermn_tpu.serving.engine import Engine, EngineConfig

        lm = TransformerLM(vocab=64, d_model=32, n_heads=4, n_layers=2,
                           d_ff=64, max_len=64, attention="reference",
                           pos_emb="rope")
        lp = lm.init(jax.random.PRNGKey(0),
                     jnp.zeros((1, 4), jnp.int32))["params"]
        rng = np.random.RandomState(0)
        fleet_prompts = [rng.randint(0, 64, (8,)).astype(np.int32)
                         for _ in range(4)]
        n_new = 8

        def _fleet_cfg():
            return EngineConfig(n_slots=2, capacity=32,
                                max_new_tokens=n_new, prefill_cohort=1,
                                buckets=[8, 32])

        single = Engine(lm, lp, _fleet_cfg())
        reqs = [single.submit(p, max_new_tokens=n_new)
                for p in fleet_prompts]
        single.run_until_drained()
        fleet_ref = [list(r.tokens) for r in reqs]

        with Router([Engine(lm, lp, _fleet_cfg()),
                     Engine(lm, lp, _fleet_cfg())]) as router:
            futs = [router.submit(p, max_new_tokens=n_new)
                    for p in fleet_prompts]
            routed = [list(router.result(f).tokens) for f in futs]
            fleet_summary = router.summary()
        routed_ok = routed == fleet_ref

        wire = {}
        disagg_ok = True
        for wfmt in ("f32", "int8-block"):
            rep = FleetReport()
            dfleet = DisaggregatedFleet(Engine(lm, lp, _fleet_cfg()),
                                        Engine(lm, lp, _fleet_cfg()),
                                        wire_format=wfmt, report=rep)
            streams = [dfleet.submit(p, max_new_tokens=n_new)
                       for p in fleet_prompts]
            dfleet.run_until_drained()
            wire[wfmt] = rep.handoff_wire_bytes[wfmt]
            if wfmt == "f32":
                disagg_ok = [list(s.tokens) for s in streams] == fleet_ref
        wire_ratio = wire["int8-block"] / wire["f32"] if wire["f32"] else 1.0
        record["fleet_honest_null"] = jax.default_backend() != "tpu"
        record["fleet_routed_identical"] = bool(routed_ok)
        record["fleet_disagg_bitwise"] = bool(disagg_ok)
        record["fleet_tokens_per_s"] = fleet_summary["tokens_per_s"]
        record["fleet_handoff_f32_bytes"] = wire["f32"]
        record["fleet_handoff_int8_bytes"] = wire["int8-block"]
        record["fleet_handoff_int8_vs_f32"] = round(wire_ratio, 6)
        record["fleet_gate_ok"] = bool(routed_ok and disagg_ok
                                       and wire_ratio <= 0.27)
    except Exception as e:  # never sink the headline metric
        record["fleet_gate_error"] = f"{type(e).__name__}: {e}"[:300]

    # async-conveyor gate (docs/serving.md#the-fleet-across-hosts): with
    # a canned 5 ms wire (InProcessTransport wire_delay_ms — the same
    # frames/NACK protocol as the cross-host plane, latency included),
    # the asynchronous conveyor's step-thread stall must be <= 0.5x the
    # synchronous conveyor's on the same workload, the streams stay
    # bitwise the single-engine reference, and the overlap fraction is
    # recorded. Cross-process throughput itself stays an honest null
    # off-TPU (two local processes on one CPU say nothing about DCN).
    try:
        from chainermn_tpu.fleet import InProcessTransport

        def _conveyor(asynchronous):
            dfl = DisaggregatedFleet(
                Engine(lm, lp, _fleet_cfg()), Engine(lm, lp, _fleet_cfg()),
                transport=InProcessTransport(wire_delay_ms=5.0),
                async_conveyor=asynchronous, max_pending=2)
            streams = [dfl.submit(p, max_new_tokens=n_new)
                       for p in fleet_prompts]
            dfl.run_until_drained()
            if asynchronous:
                dfl.close()
            toks = [list(s.tokens) for s in streams]
            return dfl.stats["stall_ms_total"], dfl.overlap_fraction, toks

        sync_stall, _, sync_toks = _conveyor(False)
        async_stall, overlap, async_toks = _conveyor(True)
        stall_ratio = (async_stall / sync_stall if sync_stall > 0
                       else float("inf"))
        conveyor_bitwise = (sync_toks == fleet_ref
                           and async_toks == fleet_ref)
        record["fleet_conveyor_sync_ms"] = round(sync_stall, 3)
        record["fleet_conveyor_async_stall_ms"] = round(async_stall, 3)
        record["fleet_conveyor_stall_ratio"] = round(stall_ratio, 6)
        record["fleet_transfer_overlap_fraction"] = round(overlap, 6)
        record["fleet_cross_process_honest_null"] = (
            jax.default_backend() != "tpu")
        record["fleet_gate_ok"] = bool(record.get("fleet_gate_ok")
                                       and conveyor_bitwise
                                       and stall_ratio <= 0.5)
    except Exception as e:  # never sink the headline metric
        record["fleet_conveyor_error"] = f"{type(e).__name__}: {e}"[:300]

    # netplane gate (docs/serving.md#transports): the streamed (format-5
    # per-layer chunk) conveyor must clear the SAME overlap bar as the
    # monolithic async gate above (stall <= 0.5x sync on the canned 5 ms
    # wire), and the m×n fleet over a REAL localhost TCP wire
    # (SocketObjectPlane, 2 prefill × 2 decode pools, streamed + async)
    # must land every stream bitwise the single-engine reference with
    # byte-exact streamed wire accounting (chunks + closing == the
    # monolithic blob, per handoff) — wire-health counters recorded.
    try:
        from chainermn_tpu.comm.socket_plane import (SocketObjectPlane,
                                                     pick_free_endpoints)
        from chainermn_tpu.fleet import (ObjectPlaneTransport,
                                         PairedTransport)

        def _streamed_conveyor(asynchronous):
            dfl = DisaggregatedFleet(
                Engine(lm, lp, _fleet_cfg()), Engine(lm, lp, _fleet_cfg()),
                transport=InProcessTransport(wire_delay_ms=5.0),
                streamed=True, async_conveyor=asynchronous, max_pending=2)
            streams = [dfl.submit(p, max_new_tokens=n_new)
                       for p in fleet_prompts]
            dfl.run_until_drained()
            if asynchronous:
                dfl.close()
            return dfl.stats["stall_ms_total"], [list(s.tokens)
                                                 for s in streams]

        st_sync, st_sync_toks = _streamed_conveyor(False)
        st_async, st_async_toks = _streamed_conveyor(True)
        st_ratio = st_async / st_sync if st_sync > 0 else float("inf")
        streamed_bitwise = (st_sync_toks == fleet_ref
                            and st_async_toks == fleet_ref)

        eps = pick_free_endpoints(2)
        pa, pb = SocketObjectPlane(eps, 0), SocketObjectPlane(eps, 1)
        try:
            pairs = [PairedTransport(
                ObjectPlaneTransport(pa, peer=1, data_tag=7100 + 10 * d,
                                     ack_tag=7101 + 10 * d),
                ObjectPlaneTransport(pb, peer=0, data_tag=7100 + 10 * d,
                                     ack_tag=7101 + 10 * d))
                for d in range(2)]
            net_rep = FleetReport()
            dfl = DisaggregatedFleet(
                [Engine(lm, lp, _fleet_cfg()), Engine(lm, lp, _fleet_cfg())],
                [Engine(lm, lp, _fleet_cfg()), Engine(lm, lp, _fleet_cfg())],
                transport=pairs, report=net_rep, streamed=True,
                async_conveyor=True, max_pending=2)
            streams = [dfl.submit(p, max_new_tokens=n_new)
                       for p in fleet_prompts]
            dfl.run_until_drained()
            dfl.close()
            net_toks = [list(s.tokens) for s in streams]
            net_totals = dfl.transport_totals()
            net_bytes = net_rep.handoff_wire_bytes.get("f32", 0)
        finally:
            pa.close()
            pb.close()
        net_bitwise = net_toks == fleet_ref
        # streamed wire accounting is byte-EXACT: the same workload's
        # monolithic f32 handoffs moved identical bytes
        exact_bytes = net_bytes == record.get("fleet_handoff_f32_bytes")
        record["netplane_streamed_stall_ratio"] = round(st_ratio, 6)
        record["netplane_socket_bitwise"] = bool(net_bitwise)
        record["netplane_streamed_wire_bytes"] = net_bytes
        record["netplane_retransmits"] = net_totals["retransmits"]
        record["netplane_reconnects"] = net_totals["reconnects"]
        record["netplane_chunk_nacks"] = net_totals["chunk_nacks"]
        record["netplane_gate_ok"] = bool(streamed_bitwise and net_bitwise
                                          and exact_bytes
                                          and st_ratio <= 0.5)
    except Exception as e:  # never sink the headline metric
        record["netplane_gate_error"] = f"{type(e).__name__}: {e}"[:300]

    # migration gate (docs/serving.md#draining-and-migration), folded
    # into the same JSON line. Three structural claims: (1) a stream
    # frozen mid-decode by export_session and adopted over the f32
    # session wire (manifest format 3) finishes BITWISE the
    # single-engine stream, with every token billed exactly once
    # across the two engines; (2) both session wire formats report
    # exact payload bytes, and the int8-block session wire holds the
    # same <= 0.27x ratio as the prefill handoff wire; (3) Router.drain
    # under a corrupt-once chaos wire (the NACK re-send heals it — no
    # replay fallback) lands the replica DRAINED with every stream
    # bitwise and the fleet-wide token count conserved: zero dropped,
    # zero duplicated.
    try:
        from chainermn_tpu.fleet.handoff import (decode_handoff,
                                                 encode_handoff,
                                                 handoff_payload_bytes)
        from chainermn_tpu.resilience import chaos as _chaos

        mrng = np.random.RandomState(17)
        mig_prompts = [mrng.randint(0, 64, (8,)).astype(np.int32)
                       for _ in range(12)]
        mig_new = 16                   # room to export past token 1

        ref_eng = Engine(lm, lp, _fleet_cfg())
        rr = [ref_eng.submit(p, max_new_tokens=mig_new)
              for p in mig_prompts]
        ref_eng.run_until_drained()
        mig_ref = [list(r.tokens) for r in rr]

        src = Engine(lm, lp, _fleet_cfg())
        dst = Engine(lm, lp, _fleet_cfg())
        mreqs = [src.submit(p, max_new_tokens=mig_new)
                 for p in mig_prompts[:2]]
        # export at a BLOCK-ALIGNED fill: each KV row is 32 elements
        # (4 kv heads x d_head 8), so fill % 8 == 0 makes every leaf an
        # exact multiple of the 256-element quant block and the 0.27x
        # wire ratio is the same claim as the prefill-handoff gate
        # (unaligned fills pad the last block — pinned in tests, not
        # gated here)
        for _ in range(200):
            ntok = len(mreqs[0].tokens)
            if (mreqs[0].slot is not None
                    and src.active.get(mreqs[0].slot) is mreqs[0]
                    and ntok >= 1
                    and (8 + ntok - 1) % 8 == 0):
                break
            src.step()
        session = src.export_session(mreqs[0])
        mig_bytes = {}
        mig_exact = True
        for wfmt in ("f32", "int8-block"):
            m, blob = encode_handoff(session, wfmt)
            mig_bytes[wfmt] = len(blob)
            mig_exact = mig_exact and handoff_payload_bytes(m) == len(blob)
        m, blob = encode_handoff(session, "f32")
        adopted = dst.import_session(decode_handoff(m, blob),
                                     mig_prompts[0])
        src.release_held(mreqs[0])
        src.run_until_drained()
        dst.run_until_drained()
        mig_streams = [list(adopted.tokens), list(mreqs[1].tokens)]
        mig_bitwise = mig_streams == mig_ref[:2]
        mig_conserved = (src.report.raw()["tokens_emitted"]
                         + dst.report.raw()["tokens_emitted"]
                         == sum(len(t) for t in mig_streams))
        mig_ratio = (mig_bytes["int8-block"] / mig_bytes["f32"]
                     if mig_bytes["f32"] else 1.0)

        drill = [Engine(lm, lp, _fleet_cfg()),
                 Engine(lm, lp, _fleet_cfg())]
        os.environ[_chaos.ENV_VAR] = "corrupt_handoff@offset=0,times=1"
        try:
            with Router(drill) as router:
                futs = [router.submit(p, max_new_tokens=mig_new)
                        for p in mig_prompts]
                # don't let drain win the race with the dispatch loop:
                # the drill is only a drill once the victim holds work
                t_wait = time.monotonic() + 30.0
                while (drill[1].report.submitted == 0
                       and time.monotonic() < t_wait):
                    time.sleep(0.002)
                dout = router.drain(1, deadline_ms=120_000)
                drained = [list(router.result(f, timeout_ms=120_000)
                                .tokens) for f in futs]
                states = router.summary()["fleet"]["replica_states"]
        finally:
            os.environ.pop(_chaos.ENV_VAR, None)
        drain_bitwise = drained == mig_ref
        drain_conserved = (sum(e.report.raw()["tokens_emitted"]
                               for e in drill)
                           == sum(len(t) for t in drained))
        record["migration_bitwise"] = bool(mig_bitwise)
        record["migration_tokens_conserved"] = bool(mig_conserved)
        record["migration_wire_bytes_exact"] = bool(mig_exact)
        record["migration_f32_bytes"] = mig_bytes["f32"]
        record["migration_int8_bytes"] = mig_bytes["int8-block"]
        record["migration_int8_vs_f32"] = round(mig_ratio, 6)
        record["migration_drain_state"] = states[1]
        record["migration_drain_bitwise"] = bool(drain_bitwise)
        record["migration_drain_conserved"] = bool(drain_conserved)
        record["migration_drain_migrated"] = dout["migrated"]
        record["migration_drain_requeued"] = dout["requeued"]
        record["migration_drain_fallbacks"] = (
            router.report.migration_fallbacks)
        record["migration_gate_ok"] = bool(
            mig_bitwise and mig_conserved and mig_exact
            and mig_ratio <= 0.27 and drain_bitwise
            and states[1] == "DRAINED" and drain_conserved
            and dout["migrated"] + dout["requeued"] > 0
            and router.report.migration_fallbacks == 0)
    except Exception as e:  # never sink the headline metric
        record["migration_gate_error"] = f"{type(e).__name__}: {e}"[:300]

    # rolling-update gate (docs/serving.md#rolling-weight-updates),
    # folded into the same JSON line. Three structural claims: (1) a
    # 3-replica fleet under live traffic walks v1 → v2 with every
    # stream finishing bitwise against exactly ONE version's reference
    # (the skew fence turns would-be mixed streams into whole replays
    # — zero dropped, zero duplicated); (2) relay wire accounting is
    # byte-exact and the publisher's egress is exactly one encoded
    # snapshot regardless of fleet size (each finished receiver
    # forwards the next hop); (3) a persistently corrupted relay rolls
    # a second rollout back through the same drain path, and the fleet
    # ends fully on v2, still serving bitwise.
    try:
        from chainermn_tpu.fleet import RolloutController
        from chainermn_tpu.resilience import chaos as _chaos
        from chainermn_tpu.serving.weights import encode_weights

        lp2 = lm.init(jax.random.PRNGKey(1),
                      jnp.zeros((1, 4), jnp.int32))["params"]

        def _oracle(params):
            eng = Engine(lm, params, _fleet_cfg())
            rr = [eng.submit(p, max_new_tokens=n_new)
                  for p in fleet_prompts]
            eng.run_until_drained()
            return [list(r.tokens) for r in rr]

        ref_v1, ref_v2 = _oracle(lp), _oracle(lp2)
        can_p = [(list(p), 0, n_new) for p in fleet_prompts[:2]]
        can_o = ref_v2[:2]

        def _mk(params, version):
            return Engine(lm, params, _fleet_cfg(),
                          weights_version=version)

        ref_v3_0 = None                # v3 canary oracle, minted early
        lp3 = lm.init(jax.random.PRNGKey(2),
                      jnp.zeros((1, 4), jnp.int32))["params"]
        ref_v3_0 = _oracle(lp3)[0]

        # single-host drill: canary tracing holds the GIL, so worker
        # heartbeats starve — give health a compile-sized timeout
        ro_engines = [_mk(lp, "v1") for _ in range(3)]
        with Router(ro_engines, health_timeout_ms=300_000) as router:
            rc = RolloutController(router, _mk, like=lp,
                                   chunk_bytes=1 << 16)
            futs = [router.submit(p, max_new_tokens=n_new)
                    for p in fleet_prompts]
            rout = rc.rollout(lp2, "v2", canary_prompts=can_p,
                              canary_oracle=can_o)
            ro_streams = [list(router.result(f, timeout_ms=120_000)
                               .tokens) for f in futs]
            ro_versions = router.summary()["fleet"]["weights_versions"]

            # wire accounting: egress = the one snapshot's frames
            _man, _data = encode_weights(lp2, weights_version="v2")
            _chunks, _closing = rc._frames(_man, _data)
            snap_bytes = (sum(len(b) for _m, b in _chunks)
                          + len(_closing[1]))
            wire_exact = (rout["publisher_egress_bytes"] == snap_bytes
                          and rout["relay_wire_bytes"]
                          == 3 * snap_bytes)

            # corrupted second rollout → rolled back, still on v2
            hop_frames = len(_chunks) + 1
            os.environ[_chaos.ENV_VAR] = (
                f"corrupt_rollout_chunk@offset=8,after={hop_frames},"
                "prob=1.0")
            try:
                rout2 = RolloutController(
                    router, _mk, like=lp, chunk_bytes=1 << 16).rollout(
                        lp3, "v3", canary_prompts=can_p[:1],
                        canary_oracle=[ref_v3_0])
            finally:
                os.environ.pop(_chaos.ENV_VAR, None)
            ro_versions2 = router.summary()["fleet"]["weights_versions"]
            fut = router.submit(fleet_prompts[0], max_new_tokens=n_new)
            after = list(router.result(fut, timeout_ms=120_000).tokens)

        ro_bitwise = all(s in (r1, r2) for s, r1, r2
                         in zip(ro_streams, ref_v1, ref_v2))
        record["rollout_status"] = rout["status"]
        record["rollout_bitwise"] = bool(ro_bitwise)
        record["rollout_egress_bytes"] = rout["publisher_egress_bytes"]
        record["rollout_wire_bytes"] = rout["relay_wire_bytes"]
        record["rollout_wire_exact"] = bool(wire_exact)
        record["rollout_rollback_status"] = rout2["status"]
        record["rollout_gate_ok"] = bool(
            rout["status"] == "completed" and ro_bitwise and wire_exact
            and all(v == "v2" for v in ro_versions.values())
            and rout2["status"] == "rolled_back"
            and all(v == "v2" for v in ro_versions2.values())
            and after == ref_v2[0])
    except Exception as e:  # never sink the headline metric
        record["rollout_gate_error"] = f"{type(e).__name__}: {e}"[:300]

    # speculative-decoding gate (docs/serving.md#speculative-decoding-
    # servingspeculativepy), folded into the same JSON line. Structural
    # claims, backend-independent: (1) SpeculativeEngine streams are
    # BITWISE the plain single-engine streams, greedy AND sampled —
    # acceptance may change the dispatch count, never the tokens — with
    # the DL108 discipline intact (ONE propose trace, ONE verify trace
    # per engine); (2) on the canned high-acceptance pair (draft
    # sharing the target's weights) the acceptance rate clears 0.9 and
    # each dispatch commits more than one token; (3) int8-block pages
    # hold >= 3.5x the slots of f32 pages at equal memory, scale
    # sidecars included. Speculative *throughput* stays an honest null
    # off-TPU: a CPU draft's latency says nothing about the TPU
    # draft/target cost ratio the economics depend on.
    try:
        from chainermn_tpu.serving.kv_cache import ServingStep
        from chainermn_tpu.serving.speculative import SpeculativeEngine

        draft_lm = TransformerLM(vocab=64, d_model=32, n_heads=4,
                                 n_layers=1, d_ff=64, max_len=64,
                                 attention="reference", pos_emb="rope")
        draft_p = draft_lm.init(jax.random.PRNGKey(1),
                                jnp.zeros((1, 4), jnp.int32))["params"]

        # (1) bitwise vs the plain engine, greedy then sampled
        sp_g = SpeculativeEngine(lm, lp, draft_lm, draft_p,
                                 _fleet_cfg(), spec_k=3)
        g_reqs = [sp_g.submit(p, max_new_tokens=n_new)
                  for p in fleet_prompts]
        sp_g.run_until_drained()
        spec_greedy_ok = [list(r.tokens) for r in g_reqs] == fleet_ref

        s_kw = dict(temperature=0.8, top_k=6)
        s_oracle = Engine(lm, lp, _fleet_cfg())
        s_ref = [s_oracle.submit(p, max_new_tokens=n_new, seed=31 + i,
                                 **s_kw)
                 for i, p in enumerate(fleet_prompts)]
        s_oracle.run_until_drained()
        sp_s = SpeculativeEngine(lm, lp, draft_lm, draft_p,
                                 _fleet_cfg(), spec_k=3)
        s_reqs = [sp_s.submit(p, max_new_tokens=n_new, seed=31 + i,
                              **s_kw)
                  for i, p in enumerate(fleet_prompts)]
        sp_s.run_until_drained()
        spec_sampled_ok = ([list(r.tokens) for r in s_reqs]
                           == [list(r.tokens) for r in s_ref])
        spec_traces_ok = (sp_g.draft.propose_traces == 1
                          and sp_g.verify_traces == 1
                          and sp_s.draft.propose_traces == 1
                          and sp_s.verify_traces == 1)

        # (2) canned high-acceptance pair: draft == target; max_new =
        # 1 + 2*(spec_k+1) so the prefill token plus two FULL rounds
        # exactly spend the budget (no truncated tail round)
        hi_cfg = EngineConfig(n_slots=2, capacity=32, max_new_tokens=9,
                              prefill_cohort=1, buckets=[8, 32])
        sp_hi = SpeculativeEngine(lm, lp, lm, lp, hi_cfg, spec_k=3)
        for i, p in enumerate(fleet_prompts):
            sp_hi.submit(p, max_new_tokens=9, seed=31 + i, **s_kw)
        sp_hi.run_until_drained()
        hi = sp_hi.report.summary()

        # (3) slots at equal memory: resident int8 pages vs f32 pages
        f32_bytes = ServingStep(lm, lp, 2, 32).cache_bytes()
        q8_bytes = ServingStep(lm, lp, 2, 32,
                               kv_dtype="int8-block").cache_bytes()
        slot_ratio = f32_bytes / q8_bytes if q8_bytes else 0.0

        record["specdec_honest_null"] = jax.default_backend() != "tpu"
        record["specdec_greedy_bitwise"] = bool(spec_greedy_ok)
        record["specdec_sampled_bitwise"] = bool(spec_sampled_ok)
        record["specdec_traces_ok"] = bool(spec_traces_ok)
        record["specdec_acceptance_rate"] = round(
            hi["acceptance_rate"], 6)
        record["specdec_tokens_per_dispatch"] = round(
            hi["tokens_per_dispatch"], 6)
        record["specdec_int8_slot_ratio"] = round(slot_ratio, 6)
        record["specdec_gate_ok"] = bool(
            spec_greedy_ok and spec_sampled_ok and spec_traces_ok
            and hi["acceptance_rate"] >= 0.9
            and hi["tokens_per_dispatch"] > 1.0
            and slot_ratio >= 3.5)
    except Exception as e:  # never sink the headline metric
        record["specdec_gate_error"] = f"{type(e).__name__}: {e}"[:300]

    # async checkpoint plane gate
    # (docs/fault_tolerance.md#checkpoint-cadence), folded into the same
    # JSON line: the per-step stall of saving through
    # checkpointing.AsyncSnapshotPlane must be <= 0.25x the synchronous
    # save's wall time on the same state. The state is a ~16 MB sharded
    # leaf — big enough that the sync path's device-get + serialize +
    # fsync + SHA-256 costs tens of ms; the async stall is just the
    # device-side copy dispatch + offload kick. Host/disk-side, so the
    # gate is NOT TPU-gated and holds on the 8-device CPU mesh.
    try:
        import shutil
        import tempfile

        from jax.sharding import NamedSharding, PartitionSpec

        from chainermn_tpu.checkpointing import AsyncSnapshotPlane
        from chainermn_tpu.extensions.checkpoint import \
            MultiNodeCheckpointer

        mesh = comm.mesh
        axis0 = mesh.axis_names[0]
        n0 = int(mesh.devices.shape[0])
        big = jax.device_put(
            jnp.zeros((n0, (4 << 20) // n0), jnp.float32),
            NamedSharding(mesh, PartitionSpec(axis0)))
        ckpt_state = {"w": big}
        ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
        try:
            ck_sync = MultiNodeCheckpointer("sync", comm, path=ckpt_dir)
            ck_sync.save(ckpt_state, iteration=0)  # warm the write path
            t0 = time.perf_counter()
            reps = 3
            for i in range(reps):
                ck_sync.save(ckpt_state, iteration=i + 1)
            sync_ms = (time.perf_counter() - t0) * 1000.0 / reps

            plane = AsyncSnapshotPlane(
                MultiNodeCheckpointer("async", comm, path=ckpt_dir))
            plane.save(ckpt_state, iteration=0)  # warm the copy trace
            plane.flush()
            stalls = []
            for i in range(reps):
                t0 = time.perf_counter()
                plane.save(ckpt_state, iteration=(i + 1) * 10)
                stalls.append((time.perf_counter() - t0) * 1000.0)
                # the cadence a real run would have: a step's worth of
                # compute between saves, which the writer overlaps
                time.sleep(sync_ms / 1000.0)
            plane.flush()
            async_ms = sum(stalls) / len(stalls)
            record["ckpt_sync_save_ms"] = round(sync_ms, 3)
            record["ckpt_async_stall_ms"] = round(async_ms, 3)
            record["ckpt_stall_ratio"] = round(
                async_ms / sync_ms if sync_ms else 1.0, 4)
            record["ckpt_bytes"] = int(plane.bytes_last)
            record["ckpt_cadence_steps"] = int(plane.cadence_last)
            record["ckpt_published"] = int(plane.published)
            record["ckpt_gate_ok"] = bool(async_ms <= 0.25 * sync_ms)
            plane.close()
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)
    except Exception as e:  # never sink the headline metric
        record["ckpt_gate_error"] = f"{type(e).__name__}: {e}"[:300]

    # static-analysis gate (docs/static_analysis.md), folded into the
    # same JSON line: the library the numbers above exercise must be
    # dlint-clean — the per-function AST passes AND the whole-program
    # DL113–DL116 passes (call-graph divergence, send/recv cycles, lock
    # inversions, blocking waits under locks) over chainermn_tpu/, with
    # no dead suppressions. Pure host-side parsing, NOT TPU-gated; a
    # benchmark record from a repo with a known deadlock pattern is not
    # a record worth keeping.
    try:
        from chainermn_tpu.analysis import run_lint

        lint = run_lint([os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "chainermn_tpu")])
        inter = [f for f in lint.findings
                 if f.rule in ("DL113", "DL114", "DL115", "DL116")]
        record["static_analysis_findings"] = len(lint.findings)
        record["static_analysis_dead_suppressions"] = len(
            lint.dead_suppressions)
        record["static_analysis_gate_ok"] = bool(
            not lint.findings and not lint.dead_suppressions)
        record["interprocedural_findings"] = len(inter)
        record["interprocedural_gate_ok"] = not inter
    except Exception as e:  # never sink the headline metric
        record["static_analysis_gate_error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(record))


if __name__ == "__main__":
    main()
