#!/usr/bin/env python
"""Record one short profiler trace of a few train steps and a few serving
iterations at small widths, and print what its planes and lines hold.

Run on the chip; the trace lands under ``chiprun_out/trace_probe/``. A trimmed
copy of such a trace is the fixture the trace reduction is tested on.
"""
import glob
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.ops import fused_lm_loss
    from chainermn_tpu.serving import Engine, EngineConfig
    from chainermn_tpu.training import StandardUpdater
    from chainermn_tpu.training.step import make_data_parallel_train_step
    from chainermn_tpu.utils import use_compile_cache

    use_compile_cache()
    out = os.path.join(ROOT, "chiprun_out", "trace_probe")
    os.makedirs(out, exist_ok=True)
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}), flush=True)

    widths = dict(vocab=8192, d_model=512, n_heads=8, n_layers=4, d_ff=2048,
                  max_len=512)
    comm = chainermn_tpu.create_communicator("xla")
    model = TransformerLM(**widths, pos_emb="learned", attention="flash",
                          dtype=jnp.bfloat16, qkv_layout="bhld")
    L, B = 512, 4 * comm.size
    rs = np.random.RandomState(0)
    rows = rs.randint(0, widths["vocab"], (8 * B, L + 1)).astype(np.int32)
    data = [(r[:-1], r[1:]) for r in rows]
    params = comm.bcast_data(jax.jit(model.init)(
        jax.random.PRNGKey(0), np.zeros((1, L), np.int32))["params"])
    opt = chainermn_tpu.create_multi_node_optimizer(optax.adamw(3e-4), comm)
    step = make_data_parallel_train_step(model, opt, comm, loss_fn=fused_lm_loss)
    updater = StandardUpdater(SerialIterator(data, B, shuffle=False), step,
                              (params, opt.init(params)), comm)
    for _ in range(3):
        updater.update()
    jax.block_until_ready(updater.state)

    smodel = TransformerLM(**dict(widths, n_kv_heads=2), pos_emb="rope",
                           attention="flash", dtype=jnp.bfloat16)
    sparams = jax.jit(smodel.init)(jax.random.PRNGKey(1),
                                   np.zeros((1, 8), np.int32))["params"]
    sparams = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), sparams)
    from jax.sharding import Mesh
    eng = Engine(smodel, sparams,
                 EngineConfig(n_slots=8, capacity=512, buckets=(128, 512),
                              decode_k=4, prefill_cohort=2,
                              cache_dtype=jnp.bfloat16),
                 mesh=Mesh(np.array(jax.devices()[:1]), ("serve",)))

    def serve(n):
        reqs = [eng.submit(rs.randint(0, 8192, (60 + 40 * (i % 3),)),
                           max_new_tokens=24, temperature=0.8, top_k=50,
                           seed=i) for i in range(n)]
        it = 0
        while not eng.idle():
            with jax.profiler.TraceAnnotation("engine.step"):
                eng.step()
            it += 1
        return it

    serve(10)

    jax.profiler.start_trace(out)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(6):
            with jax.profiler.TraceAnnotation("updater.update"):
                updater.update()
        jax.block_until_ready(updater.state)
        time.sleep(0.05)
        iters = serve(10)
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    print(json.dumps({"window_s": t1 - t0, "serve_iters": iters}), flush=True)

    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(out, "plugins/profile/*/*.xplane.pb")))[-1]
    print(json.dumps({"xplane": path, "bytes": os.path.getsize(path)}))
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), "lines", len(lines))
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            t_first = min(e.start_ns for e in evs)
            t_last = max(e.start_ns + e.duration_ns for e in evs)
            print("  LINE", repr(line.name), "events", len(evs), "first_ns",
                  t_first, "last_ns", t_last, "top", top)
            if len(lines) < 12 or "XLA" in line.name or "Step" in line.name:
                for e in evs[:3]:
                    print("     EV", e.name, e.start_ns, e.duration_ns,
                          dict(list(e.stats)[:8]))


if __name__ == "__main__":
    main()
