#!/usr/bin/env python
"""LM train-step profile + cost analysis on the real chip.

Builds the EXACT tools/bench_lm.py program (GPT-small-ish, d=768, 12L,
L=2048, b=8, bf16, flash attention, adamw, scan_steps=4), then:

1. `cost_analysis()` on the compiled step → FLOPs + HBM bytes → roofline.
2. A jax.profiler trace around one warmed dispatch → per-kernel device
   time, bucketed by kernel family.

Methodology follows docs/resnet50_roofline.md (device pid from the
trace; leaf events only, jit_*/numeric containers excluded).

Usage: python tools/profile_lm.py [trace_dir]
"""

import collections
import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

D_MODEL, N_LAYERS, SEQ_LEN = 768, 12, 2048
BATCH = int(os.environ.get("PROFILE_BATCH", "8"))
SCAN_K = 4
QKV_LAYOUT = os.environ.get("PROFILE_QKV_LAYOUT", "blhd")
LOSS = os.environ.get("PROFILE_LOSS", "unfused")  # 'fused' → ops.fused_ce


def build_step():
    import jax
    import jax.numpy as jnp
    import optax

    import chainermn_tpu
    from chainermn_tpu.models.transformer import (
        TransformerLM, lm_loss_with_aux)
    from chainermn_tpu.training.step import make_data_parallel_train_step

    comm = chainermn_tpu.create_communicator("xla")
    model = TransformerLM(
        vocab=32768, d_model=D_MODEL, n_heads=D_MODEL // 64,
        n_layers=N_LAYERS, d_ff=4 * D_MODEL, max_len=SEQ_LEN,
        pos_emb="rope", attention="flash", dtype=jnp.bfloat16,
        qkv_layout=QKV_LAYOUT)
    toks = np.random.RandomState(0).randint(
        0, 32768, size=(BATCH * comm.size, SEQ_LEN + 1)).astype(np.int32)
    params = comm.bcast_data(
        model.init(jax.random.PRNGKey(0), toks[:1, :-1])["params"])
    opt = chainermn_tpu.create_multi_node_optimizer(optax.adamw(3e-4), comm)
    if LOSS == "fused":
        from chainermn_tpu.ops import fused_lm_loss

        lf = fused_lm_loss
    else:
        lf = lm_loss_with_aux
    step = make_data_parallel_train_step(
        model, opt, comm, loss_fn=lf, scan_steps=SCAN_K)
    state = (params, opt.init(params))

    from jax.sharding import NamedSharding, PartitionSpec as P
    dsh = NamedSharding(comm.mesh, P(None, comm.axis_names[0]))
    xs = jax.device_put(np.broadcast_to(
        toks[None, :, :-1], (SCAN_K,) + toks[:, :-1].shape).copy(), dsh)
    ys = jax.device_put(np.broadcast_to(
        toks[None, :, 1:], (SCAN_K,) + toks[:, 1:].shape).copy(), dsh)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    return step, state, xs, ys, n_params


def parse_trace(trace_dir):
    """Sum leaf device-kernel durations from the newest vm.trace.json.gz,
    bucketed by kernel-name family (docs/resnet50_roofline.md §1)."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True),
        key=os.path.getmtime)
    if not paths:
        return None
    with gzip.open(paths[-1], "rt") as f:
        tr = json.load(f)
    events = tr.get("traceEvents", [])
    # device pid: the process whose name mentions the device (pid 3 on
    # this plugin); fall back to the pid with the most X events
    pid_names = {e["pid"]: e["args"].get("name", "")
                 for e in events if e.get("ph") == "M"
                 and e.get("name") == "process_name" and "args" in e}
    dev_pids = [p for p, n in pid_names.items()
                if "TPU" in n or "Device" in n or "/device" in n.lower()]
    xs = [e for e in events if e.get("ph") == "X"]
    if not dev_pids:
        counts = collections.Counter(e["pid"] for e in xs)
        dev_pids = [counts.most_common(1)[0][0]] if counts else []
    fams = collections.Counter()
    total = 0.0
    for e in xs:
        if e["pid"] not in dev_pids:
            continue
        name = e.get("name", "")
        # containers, not kernels
        if name.startswith("jit_") or name.isdigit():
            continue
        if name.startswith("while"):
            continue  # container: its leaves are counted individually
        dur = e.get("dur", 0) / 1e6  # us → s
        base = name.split(".")[0].split("(")[0]
        # strip trailing instance numbers: fusion.123 → fusion
        base = base.rstrip("0123456789").rstrip("._-") or name
        fams[base] += dur
        total += dur
    return {"total_s": total, "families": dict(fams.most_common(25))}


def main():
    import jax

    from chainermn_tpu.utils import use_compile_cache

    use_compile_cache()

    trace_dir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/lm_trace"
    step, state, xs, ys, n_params = build_step()

    state, m = step(state, xs, ys)  # warmup: the compile
    float(m["main/loss"][-1])

    # ---- cost analysis on the compiled executable --------------------
    ca = {}
    try:
        compiled = step.lower(state, xs, ys).compile()
        raw = compiled.cost_analysis()
        raw = raw[0] if isinstance(raw, (list, tuple)) else raw
        ca = {k: float(v) for k, v in raw.items()
              if isinstance(v, (int, float)) and (
                  "flops" in k or "bytes" in k or "time" in k)}
    except Exception as e:  # noqa: BLE001 — report, don't die
        ca = {"error": repr(e)}

    # ---- timed steady state ------------------------------------------
    # bench_lm methodology: sync ONCE at the end — dispatches queue
    # asynchronously, so host dispatch overlaps device work and the
    # figure is device throughput.
    n_iters = 6
    t0 = time.perf_counter()
    for _ in range(n_iters):
        state, m = step(state, xs, ys)  # dlint: disable=DL104 — see above
    float(m["main/loss"][-1])
    dt = time.perf_counter() - t0
    step_s = dt / (n_iters * SCAN_K)
    tok_s = BATCH * SEQ_LEN / step_s

    # ---- trace one dispatch ------------------------------------------
    jax.profiler.start_trace(trace_dir)
    state, m = step(state, xs, ys)
    float(m["main/loss"][-1])
    jax.profiler.stop_trace()
    prof = parse_trace(trace_dir)

    flops = ca.get("flops", 0.0) * 1  # per dispatch (SCAN_K steps)
    bytes_ = ca.get("bytes accessed", 0.0)
    out = {
        "config": {"d_model": D_MODEL, "n_layers": N_LAYERS,
                   "seq_len": SEQ_LEN, "batch": BATCH, "scan_k": SCAN_K,
                   "n_params": n_params},
        "measured_step_s": step_s,
        "tokens_per_sec": tok_s,
        "cost_analysis_per_dispatch": ca,
        "flops_per_step": flops / SCAN_K if flops else None,
        "bytes_per_step": bytes_ / SCAN_K if bytes_ else None,
        "roofline_hbm_ms": (bytes_ / SCAN_K) / 819e9 * 1e3 if bytes_
        else None,
        "roofline_mxu_ms": (flops / SCAN_K) / 197e12 * 1e3 if flops
        else None,
        "profile": prof,
    }
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
